"""Layer proofs in the window's delivered attestations, over the window."""


def read(rec):
    layers = sum(q.get("layers", 0) for q in rec["queries"] if "wire" in q)
    return layers / rec["window_s"] if rec["window_s"] > 0 else None
