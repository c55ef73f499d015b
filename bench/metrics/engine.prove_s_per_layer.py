"""Engine proving time (``EngineReport.prove_seconds``, through the
gateway's stage sums) in the window, per layer proof."""


def read(rec):
    a = rec["gateway_before"]["stage_seconds"]["prove"]["sum"]
    b = rec["gateway_after"]["stage_seconds"]["prove"]["sum"]
    layers = sum(q.get("layers", 0) for q in rec["queries"] if "wire" in q)
    return (b - a) / layers if layers else None
