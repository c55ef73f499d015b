"""Encoded v2 attestation bytes per layer over the window's attestations."""


def read(rec):
    done = [q for q in rec["queries"] if "wire" in q]
    layers = sum(q.get("layers", 0) for q in done)
    return sum(len(q["wire"]) for q in done) / 1024 / layers if layers \
        else None
