"""The Pallas calls' share of the HBM roofline: the least bytes they must
move (each operand read once, each result written once, from their HLO
shapes) over the HBM peak, divided by their summed device time."""


def read(rec):
    t = rec["trace"]
    if not t.get("pallas_s") or not t.get("pallas_bytes"):
        return None
    least_s = t["pallas_bytes"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / t["pallas_s"]
