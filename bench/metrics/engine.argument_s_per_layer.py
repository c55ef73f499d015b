"""Host seconds of the span ``layer.argument`` of the engine's layer proofs
(``core/layer_proof.prove_layer``: the block's sum-check argument and
the witness checks), through the gateway's stage sums, per layer proof
in the window; none where the program has no such span."""


def read(rec):
    a = rec["gateway_before"]["stage_seconds"].get("argument")
    b = rec["gateway_after"]["stage_seconds"].get("argument")
    layers = sum(q.get("layers", 0) for q in rec["queries"] if "wire" in q)
    if a is None or b is None or not layers:
        return None
    return (b["sum"] - a["sum"]) / layers
