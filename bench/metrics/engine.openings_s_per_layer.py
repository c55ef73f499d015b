"""Host seconds of the span ``layer.openings`` of the engine's layer proofs
(``core/layer_proof.prove_layer``: the PCS openings), through the
gateway's stage sums, per layer proof in the window; none where the
program has no such span."""


def read(rec):
    a = rec["gateway_before"]["stage_seconds"].get("openings")
    b = rec["gateway_after"]["stage_seconds"].get("openings")
    layers = sum(q.get("layers", 0) for q in rec["queries"] if "wire" in q)
    if a is None or b is None or not layers:
        return None
    return (b["sum"] - a["sum"]) / layers
