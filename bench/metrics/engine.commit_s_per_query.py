"""Engine boundary-commit time (``EngineReport.commit_seconds``; one
coalesced window's commit is shared by its queries) per window query."""


def read(rec):
    a = rec["gateway_before"]["stage_seconds"]["commit"]
    b = rec["gateway_after"]["stage_seconds"]["commit"]
    done = rec["gateway_after"]["completed"] - rec["gateway_before"][
        "completed"]
    return (b["sum"] - a["sum"]) / done if done > 0 else None
