"""Compile wait of the engine's calls (``EngineReport.compile_seconds``:
tracing, lowering and compiling or loading programs on the threads that
work for the call, and blocking on the compile-ahead pool), through the
gateway's stage sums, per window query; none where the program does not
count it."""


def read(rec):
    a = rec["gateway_before"]["stage_seconds"].get("compile")
    b = rec["gateway_after"]["stage_seconds"].get("compile")
    done = rec["gateway_after"]["completed"] - rec["gateway_before"][
        "completed"]
    if a is None or b is None or done <= 0:
        return None
    return (b["sum"] - a["sum"]) / done
