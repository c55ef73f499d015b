"""Device time of the Pallas custom calls over the device's busy time,
from the traced window."""


def read(rec):
    t = rec["trace"]
    if not t.get("pallas_s") or not t.get("busy_s"):
        return None
    return 100.0 * t["pallas_s"] / t["busy_s"]
