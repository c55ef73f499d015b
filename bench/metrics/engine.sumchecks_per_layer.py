"""Sum-checks on the engine's finished layer tapes (``SumcheckProof``
objects, counted by ``core/layer_proof.prove_layer`` after its openings),
through the gateway's ``tape_counts``, per layer proof in the window;
none where the program has no such count."""


def read(rec):
    a = rec["gateway_before"].get("tape_counts", {}).get("sumchecks")
    b = rec["gateway_after"].get("tape_counts", {}).get("sumchecks")
    layers = sum(q.get("layers", 0) for q in rec["queries"] if "wire" in q)
    if a is None or b is None or not layers:
        return None
    return (b - a) / layers
