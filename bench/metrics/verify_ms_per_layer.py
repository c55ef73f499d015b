"""The client's verification on the host CPU, summed over the window's
attestations, per verified layer."""


def read(rec):
    done = [q for q in rec["queries"] if "verify_s" in q]
    layers = sum(q["layers"] for q in done)
    return 1e3 * sum(q["verify_s"] for q in done) / layers if layers else None
