"""Mean admission wait of the window's queries: the gateway's own
admission-wait sum and count, taken between the window's two ends."""


def read(rec):
    a = rec["gateway_before"]["admission_wait_seconds"]
    b = rec["gateway_after"]["admission_wait_seconds"]
    n = b["count"] - a["count"]
    return (b["sum"] - a["sum"]) / n if n > 0 else None
