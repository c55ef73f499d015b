"""Host seconds of the span ``gateway.deliver`` (``gateway/transport.py``:
from the proof's completion through its encoding to the last chunk sent,
before ``DONE``), through the gateway's stage sums, per window query;
none where the program has no such span."""


def read(rec):
    a = rec["gateway_before"]["stage_seconds"].get("deliver")
    b = rec["gateway_after"]["stage_seconds"].get("deliver")
    done = rec["gateway_after"]["completed"] - rec["gateway_before"][
        "completed"]
    if a is None or b is None or done <= 0:
        return None
    return (b["sum"] - a["sum"]) / done
