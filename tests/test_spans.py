"""Spans of the serving path and the compile wait of each engine call
(``repro.spans``): a layer proof's four phases charged to the engine
call that dispatched it, on worker threads too; compile events charged
only on a thread with an open span of a call; the recorder; the
gateway's snapshot of the phase, compile and deliver histograms; the
layer tapes' counts in the engine's report and the gateway's snapshot.
"""
import concurrent.futures
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, spans
from repro.core import blocks as B
from repro.core import layer_proof as LP
from repro.gateway import AttestationGateway, GatewayClient
from repro.kernels import ahead as AH
from repro.runtime.engine import PHASES, TAPE_COUNTS, ProverEngine

CFG = B.BlockCfg(family="gpt2", d=16, dff=32, heads=2, kv_heads=2, dh=8,
                 seq=8)
QUERIES = 2


@pytest.fixture(scope="module")
def model():
    """A one-layer service with its model card, and two queries.  The
    weights' range proof, set-up work that no test here times, is left
    out: nothing the layer proofs need depends on it."""
    rng = np.random.default_rng(11)
    weights = [B.init_weights(CFG, rng)]
    xs = [np.clip(np.round(rng.normal(0, 0.5, (CFG.d_pad, CFG.seq)) * 256),
                  -32768, 32767).astype(np.int64) for _ in range(2)]
    svc = api.ProofService([CFG], weights, default_queries=QUERIES)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(LP, "weight_range_proof", lambda *a, **k: [])
        svc.model_card
    yield svc, xs
    svc.close()


def _fresh_jit():
    """A jitted function no cache has seen: its first call compiles."""
    salt = np.uint32(time.monotonic_ns() & 0xFFFF)
    return jax.jit(lambda x: (x * salt + 7) ^ (x >> 3))


@pytest.mark.parametrize("workers", [1, 2])
@pytest.mark.parametrize("call", ["prove", "prove_many"])
def test_phases_are_charged_to_the_call(model, call, workers):
    svc, xs = model
    eng = ProverEngine(svc.block_cfgs, svc.weights,
                       svc.engine_for(QUERIES).params,
                       weight_cache=svc.weight_cache, workers=workers)
    with spans.recording(256) as rec:
        if call == "prove":
            _, report = eng.prove(xs[0])
        else:
            _, report = eng.prove_many(xs)
    assert set(report.phase_seconds) == set(PHASES)
    assert all(report.phase_seconds[p] > 0 for p in PHASES), report
    outer = [r for r in rec if r[0] == "engine.prove"]
    assert len(outer) == 1
    call_id = outer[0][2]
    assert (outer[0][5] - outer[0][4]) / 1e9 == report.prove_seconds
    phases = [r for r in rec if r[0].startswith("layer.")]
    assert len(phases) == 4 * report.jobs
    # charged to the call that dispatched them, whichever thread proved
    assert {r[2] for r in phases} == {call_id}
    phase_sum = sum(report.phase_seconds.values())
    if workers == 1:
        assert 0.9 * report.prove_seconds <= phase_sum \
            <= report.prove_seconds
    else:
        assert {r[3] for r in phases} <= {"proof-worker-0",
                                          "proof-worker-1"}
    assert report.total_seconds == pytest.approx(
        report.forward_seconds + report.commit_seconds
        + report.prove_seconds)


@pytest.mark.parametrize("workers", [1, 2])
def test_tape_counts_are_charged_to_the_call(model, workers):
    """The sum-checks and claimed values of every layer tape the call
    proved, on whichever worker thread, summed in its report."""
    svc, xs = model
    eng = ProverEngine(svc.block_cfgs, svc.weights,
                       svc.engine_for(QUERIES).params,
                       weight_cache=svc.weight_cache, workers=workers)
    proofs, report = eng.prove_many(xs)
    tapes = [lp.tape for p in proofs for lp in p.layer_proofs]
    want = {k: sum(LP.tape_counts(t)[k] for t in tapes)
            for k in TAPE_COUNTS}
    assert report.tape_counts == want
    assert 0 < want["sumchecks"] < want["vals"]


def test_compile_in_a_span_is_charged():
    f, x = _fresh_jit(), jnp.arange(8, dtype=jnp.uint32)
    with spans.collect() as col, spans.span("t") as s:
        f(x).block_until_ready()
    assert 0 < col.seconds(spans.COMPILE) <= s.seconds
    with spans.collect() as col, spans.span("t"):
        f(x).block_until_ready()        # compiled already
    assert col.seconds(spans.COMPILE) == 0


def test_nested_compile_events_count_once():
    """An inner jit traced inside an outer one: JAX records the start of
    each event as a scalar and its duration at its end."""
    ev = "/jax/core/compile/jaxpr_trace_duration"
    with spans.collect() as col, spans.span("t"):
        jax.monitoring.record_scalar(ev, 0.0)              # outer starts
        jax.monitoring.record_scalar(ev, 0.0)              # inner starts
        jax.monitoring.record_event_duration_secs(ev, 1.0)   # inner ends
        jax.monitoring.record_event_duration_secs(ev, 2.0)   # outer ends
        jax.monitoring.record_event_duration_secs(ev, 0.5)   # no start seen
    assert col.seconds(spans.COMPILE) == 2.5


def test_compile_elsewhere_is_not_charged():
    x = jnp.arange(8, dtype=jnp.uint32)
    with spans.collect() as col:
        _fresh_jit()(x).block_until_ready()      # no span open
        with spans.span("t"):
            # on the compile-ahead pool, its future read directly
            AH.start(_fresh_jit(), jax.ShapeDtypeStruct(
                (8,), jnp.uint32)).result()
            other = threading.Thread(
                target=lambda: _fresh_jit()(x).block_until_ready())
            other.start()
            other.join(timeout=60)
            assert not other.is_alive()
    assert col.seconds(spans.COMPILE) == 0


def test_ahead_wait_charges_the_time_blocked():
    fut = concurrent.futures.Future()
    timer = threading.Timer(0.2, fut.set_result, args=(None,))
    with spans.collect() as col, spans.span("t"):
        timer.start()
        AH.wait(fut)
    timer.join(timeout=10)
    assert col.seconds(spans.COMPILE) >= 0.2
    with spans.collect() as col:
        fut = concurrent.futures.Future()
        threading.Timer(0.05, fut.set_result, args=(None,)).start()
        AH.wait(fut)                              # no span open
    assert col.seconds(spans.COMPILE) == 0


def test_recorder_off_keeps_nothing():
    with spans.recording(8) as rec:
        with spans.span("kept"):
            pass
    assert spans._RECORDER is None
    with spans.span("outside"):
        pass
    assert [r[0] for r in rec] == ["kept"]


def test_recorder_is_bounded_and_on_the_wall_clock():
    with spans.recording(4) as rec:
        for i in range(10):
            with spans.span(f"s{i}"):
                with spans.span("inner"):
                    pass
    assert len(rec) == 4
    assert [r[0] for r in rec] == ["inner", "s8", "inner", "s9"]
    assert rec[-2][1] == "s9" and rec[-1][1] is None
    assert rec[-1][3] == threading.current_thread().name
    assert abs(spans.wall_ns(time.monotonic_ns()) - time.time_ns()) < 1e6


def test_gateway_snapshot_has_phase_compile_and_deliver(model):
    svc, xs = model
    pol = api.VerifyPolicy(pcs_queries=QUERIES)
    gw = AttestationGateway(svc)
    try:
        server = gw.serve(port=0)
        client = GatewayClient(*server.address, client_id="spans")
        try:
            for i, x in enumerate(xs):
                client.attest_bytes(x, pol)
                # counted before the client had its attestation
                snap = gw.metrics_snapshot()
                assert snap["stage_seconds"]["deliver"]["count"] == i + 1
        finally:
            client.close()
    finally:
        gw.close()
    stages = json.loads(json.dumps(snap))["stage_seconds"]
    for key in (*PHASES, "compile", "deliver", "forward", "commit",
                "prove", "total"):
        assert key in stages, key
    for key in PHASES:
        assert stages[key]["count"] == 2 and stages[key]["sum"] > 0, key
    assert stages["compile"]["count"] == 2
    assert stages["deliver"]["sum"] > 0
    counts = json.loads(json.dumps(snap))["tape_counts"]
    assert set(counts) == set(TAPE_COUNTS)
    assert 0 < counts["sumchecks"] < counts["vals"]
