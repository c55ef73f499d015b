"""The reader of the layer tapes' sum-check count, on the CPU: on the
rehearsal's traced run, and on a gateway snapshot from a program that
has no such count."""
import os
import sys

from test_rehearsal import ROOT, harness  # noqa: F401 — the toy cell

sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.join(ROOT, "src"))


def test_sumchecks_reader_finds_the_tape_count(harness):  # noqa: F811
    out = harness(2**31 + 105, trace=True, seconds=8.0)
    assert out["correct"] is True
    # the toy's two layers hold as many sum-checks, but for the first
    # layer's one more range check of its input
    sc = out["metrics"]["engine.sumchecks_per_layer"]
    assert sc["unit"] == "count" and (2 * sc["value"]).is_integer()
    assert sc["value"] > 0


def test_sumchecks_reader_is_silent_without_the_count():
    """A program whose gateway snapshot has no ``tape_counts`` (before
    the count existed) reads as nothing, not as an error."""
    import run
    read = run.reader(ROOT, "engine.sumchecks_per_layer")
    snap = {"stage_seconds": {}}
    rec = {"gateway_before": snap, "gateway_after": snap,
           "queries": [{"wire": 1, "layers": 2}]}
    assert read(rec) is None
    rec["gateway_before"] = {"tape_counts": {"sumchecks": 10}}
    rec["gateway_after"] = {"tape_counts": {"sumchecks": 74}}
    assert read(rec) == 32
