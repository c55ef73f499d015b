"""Staged ProverEngine: parallel-vs-sequential equivalence, replay-on-loss
fault injection, weight-commitment caching, and the serving-path query
binding (runtime/engine.py, runtime/scheduler.py, launch/serve.py).
"""
import dataclasses
import pickle
import threading

import numpy as np
import pytest

from repro.core import blocks as B
from repro.core import chain as CH
from repro.core import layer_proof as LP
from repro.core import pcs as PCS
from repro.launch import serve as SRV
from repro.runtime.engine import ProverEngine, WeightCommitCache
from repro.runtime.fault import ProofWorkReplayQueue
from repro.runtime.scheduler import ProofScheduler

CFG = B.BlockCfg(family="gpt2", d=16, dff=32, heads=2, kv_heads=2, dh=8,
                 seq=8)
L = 2


def _tapes(proof):
    return [pickle.dumps(lp.tape) for lp in proof.layer_proofs]


@pytest.fixture(scope="module")
def engine_setup():
    params = PCS.PCSParams(blowup=4, queries=2)
    rng = np.random.default_rng(7)
    weights = [B.init_weights(CFG, rng) for _ in range(L)]
    x0 = np.clip(np.round(rng.normal(0, 0.5, (CFG.d_pad, CFG.seq)) * 256),
                 -32768, 32767).astype(np.int64)
    cache = WeightCommitCache()
    eng = ProverEngine([CFG] * L, weights, params, weight_cache=cache,
                       workers=1)
    seq_proof, seq_report = eng.prove(x0)
    return params, weights, x0, cache, eng, seq_proof, seq_report


@pytest.fixture(scope="module")
def parallel_response(engine_setup):
    """Serving-path prove with a 2-worker fleet AND an injected worker
    loss (claim #1 dropped mid-flight -> requeued and re-proven)."""
    params, weights, x0, cache, eng, _, _ = engine_setup
    serve_cfg = SRV.ServeCfg(pcs_queries=params.queries, prove_workers=2)
    tokens = np.arange(5)
    return SRV.prove_query([CFG] * L, weights, eng.wt_commits, x0,
                           serve_cfg, tokens=tokens, weight_cache=cache,
                           fail_claims={1})


def test_sequential_engine_matches_legacy_chain(engine_setup):
    """chain.prove_model (now a wrapper) == direct engine output."""
    params, weights, x0, cache, eng, seq_proof, _ = engine_setup
    legacy = CH.prove_model([CFG] * L, weights, eng.wt_commits, x0, params,
                            layer_subset=[0])
    assert pickle.dumps(legacy.layer_proofs[0].tape) == \
        pickle.dumps(seq_proof.layer_proofs[0].tape)
    for a, b in zip(legacy.boundary_roots, seq_proof.boundary_roots):
        np.testing.assert_array_equal(a, b)


def test_parallel_transcripts_identical_and_verify(engine_setup,
                                                   parallel_response):
    params, weights, x0, cache, eng, seq_proof, _ = engine_setup
    par_proof = parallel_response.model_proof
    # bit-identical transcripts regardless of worker count / worker loss
    assert _tapes(par_proof) == _tapes(seq_proof)
    for a, b in zip(par_proof.boundary_roots, seq_proof.boundary_roots):
        np.testing.assert_array_equal(a, b)
    # full composite verification incl. adjacency + query binding
    roots = [w.root for w in eng.wt_commits]
    assert CH.verify_model([CFG] * L, par_proof, roots, params,
                           in_root=par_proof.boundary_roots[0],
                           out_root=par_proof.boundary_roots[-1])


def test_worker_loss_redo_recorded(parallel_response):
    rep = parallel_response.engine_report
    assert rep.workers == 2
    assert rep.jobs == L
    assert rep.losses == 1            # injected via fail_claims={1}
    assert rep.claims == L + 1        # every loss costs exactly one redo


def test_serving_response_query_binding(engine_setup, parallel_response):
    params, weights, x0, cache, eng, _, _ = engine_setup
    resp = parallel_response
    roots = [w.root for w in eng.wt_commits]
    assert resp.tokens.shape == (5,)          # tokens now bound in
    assert resp.in_root is not None and resp.out_root is not None
    # client recomputes c_0 from its own query -> accepts
    assert SRV.verify_response([CFG] * L, resp, roots,
                               pcs_queries=params.queries, x0=x0)
    # replaying the response against a different query -> rejected
    x_other = x0.copy()
    x_other[0, 0] += 1
    assert not SRV.verify_response([CFG] * L, resp, roots,
                                   pcs_queries=params.queries, x0=x_other)
    # tampered claimed output root -> rejected
    bad = dataclasses.replace(resp, out_root=resp.model_proof.
                              boundary_roots[0])
    assert not SRV.verify_response([CFG] * L, bad, roots,
                                   pcs_queries=params.queries)


def test_process_backend_matches_sequential(engine_setup):
    """GIL-free worker fleet (spawned processes) produces bit-identical
    transcripts — the backend the throughput benchmark scales."""
    params, weights, x0, cache, eng, seq_proof, _ = engine_setup
    with ProverEngine([CFG] * L, weights, params,
                      wt_commits=eng.wt_commits, workers=2,
                      backend="process") as eng_p:
        proof, report = eng_p.prove(x0)
    assert _tapes(proof) == _tapes(seq_proof)
    assert report.workers == 2
    assert report.jobs == L


def test_process_backend_refused_on_tpu(monkeypatch):
    """A chip belongs to one process: on a TPU the process backend raises
    before any worker is spawned."""
    import multiprocessing

    from repro.kernels import ops as KOPS
    monkeypatch.setattr(KOPS, "on_tpu", lambda: True)
    rng = np.random.default_rng(0)
    with pytest.raises(RuntimeError, match="cannot run on a TPU"):
        ProverEngine([CFG], [B.init_weights(CFG, rng)],
                     PCS.PCSParams(blowup=4, queries=2), workers=2,
                     backend="process")
    assert not multiprocessing.active_children()


def test_weight_cache_hit_miss(engine_setup):
    params, weights, x0, cache, eng, _, _ = engine_setup
    # the fixture's setup was the miss path: one range proof per layer
    assert cache.misses == L
    hits_before = cache.hits
    eng2 = ProverEngine([CFG] * L, weights, params, weight_cache=cache,
                        workers=2)
    commits2 = eng2.wt_commits
    assert cache.hits == hits_before + L
    assert cache.misses == L                   # no new setup ran
    for a, b in zip(eng.wt_commits, commits2):
        assert a is b                          # cached object reused


def test_batched_boundary_commit_matches_single(engine_setup):
    params, weights, x0, *_ = engine_setup
    y, _tr = B.block_forward(CFG, weights[0], x0)
    batched = LP.commit_boundaries([CFG, CFG], [x0, y], params)
    for bc, x in zip(batched, (x0, y)):
        single = LP.commit_boundary(CFG, x, params)
        np.testing.assert_array_equal(bc.root, single.root)
        np.testing.assert_array_equal(bc.ints, single.ints)


# ---------------------------------------------------------------------------
# Queue + scheduler unit tests (no crypto — fast).
# ---------------------------------------------------------------------------
def test_queue_requeue_on_loss_order():
    q = ProofWorkReplayQueue([3, 1, 4])
    assert q.claim_with_seq("a") == (3, 0)
    assert q.claim_with_seq("b") == (1, 1)
    q.worker_lost("a")
    assert q.losses == 1
    # lost layer comes back at the FRONT (retried before fresh work)
    assert q.claim_with_seq("c") == (3, 2)
    q.complete("b", "p1")
    q.complete("c", "p3")
    assert not q.finished
    assert q.claim("c") == 4
    q.complete("c", "p4")
    assert q.finished
    assert q.done == {1: "p1", 3: "p3", 4: "p4"}
    # losing a worker with nothing in flight is a no-op
    q.worker_lost("zombie")
    assert q.losses == 1


def test_queue_thread_safety_under_contention():
    q = ProofWorkReplayQueue(list(range(200)))

    def drain(wid):
        while True:
            layer = q.claim(wid)
            if layer is None:
                if q.finished:
                    return
                continue
            q.complete(wid, layer * 10)

    threads = [threading.Thread(target=drain, args=(f"w{i}",))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert q.finished
    assert q.claims == 200
    assert q.done == {i: i * 10 for i in range(200)}


def test_scheduler_fault_injection_deterministic():
    proved = []

    def prove(layer):
        proved.append(layer)
        return f"pi_{layer}"

    sched = ProofScheduler(workers=1, fail_claims={0, 2})
    done, stats = sched.run([5, 6, 7], prove)
    assert done == {5: "pi_5", 6: "pi_6", 7: "pi_7"}
    assert stats.losses == 2
    assert stats.claims == 5           # 3 jobs + 2 redos
    assert stats.jobs == 3


def test_scheduler_parallel_completes_with_losses():
    sched = ProofScheduler(workers=4, fail_claims={0, 1, 2})
    done, stats = sched.run(list(range(16)), lambda l: l + 100)
    assert done == {l: l + 100 for l in range(16)}
    assert stats.losses == 3
    assert stats.claims == 16 + 3


def test_scheduler_propagates_prover_errors():
    def prove(layer):
        if layer == 2:
            raise ValueError("prover exploded")
        return layer

    with pytest.raises(ValueError, match="prover exploded"):
        ProofScheduler(workers=2).run([1, 2, 3], prove)
