"""The program's spans as the benchmark reads them, on the CPU: the six
readers of the phase, compile and deliver histograms on the rehearsal's
traced run, and the spans' clock against a profiler trace's."""
import glob
import os
import sys

import pytest

from test_rehearsal import ROOT, harness  # noqa: F401 — the toy cell

sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.join(ROOT, "src"))

READERS = ("engine.witness_s_per_layer", "engine.argument_s_per_layer",
           "engine.lookups_s_per_layer", "engine.openings_s_per_layer",
           "engine.compile_s_per_query", "gateway.deliver_s_per_query")


def test_readers_find_the_program_spans(harness):  # noqa: F811
    out = harness(2**31 + 104, trace=True, seconds=8.0)
    assert out["correct"] is True
    m = out["metrics"]
    for name in READERS:
        assert m[name]["value"] > 0 and m[name]["unit"] == "s", name
    phases = sum(m[name]["value"] for name in READERS[:4])
    prove = m["engine.prove_s_per_layer"]["value"]
    assert 0.9 * prove <= phases <= prove      # one worker: the whole body


def test_span_bounds_hold_the_trace_of_their_work(tmp_path):
    """Executor events of a jitted loop run inside a recorded span lie
    between the span's bounds, both put on the trace's clock through
    ``spans.wall_ns`` and ``trace_reduce.profile_start``."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import trace_reduce
    from repro import spans

    @jax.jit
    def loop(x):
        return jax.lax.fori_loop(
            0, 2000, lambda i, v: (v * 1103515245 + 12345) ^ (v >> 7), x)

    x = jnp.arange(1 << 16, dtype=jnp.uint32)
    loop(x).block_until_ready()                 # compiled before the trace
    opts = jax.profiler.ProfileOptions()
    opts.enable_hlo_proto = False
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1                  # the XLA:CPU executors
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.recording(4) as rec, spans.span("loop"):
            loop(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (name, _, _, _, start, end), = rec
    pb = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                "*.xplane.pb"))
    pd = trace_reduce.load(pb[0])
    zero = trace_reduce.profile_start(pd)
    lo, hi = spans.wall_ns(start) - zero, spans.wall_ns(end) - zero
    ops = [op for chip in trace_reduce.device_timelines(pd)
           for op in chip["ops"]]
    assert name == "loop" and ops
    assert lo <= min(a for a, _, _ in ops)
    assert max(b for _, b, _ in ops) <= hi
    # the loop's work, not a stray event, fills most of the span
    assert max(b for _, b, _ in ops) - min(a for a, _, _ in ops) \
        > 0.5 * (hi - lo)
