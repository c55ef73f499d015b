"""Staged prover engine: the paper's layerwise decomposition, made real.

``chain.prove_model`` in the seed was one sequential loop interleaving
forward execution, boundary commitment, and per-layer proving.  This
module unbundles it into the three stages the paper's §3.3 parallelism
argument actually needs:

  stage 1  quantized forward replay — run the deployed circuit semantics
           (blocks.block_forward on qops) over the query, recording every
           inter-layer activation h_0..h_L and per-layer witness traces;
  stage 2  commitment — all L+1 boundary activations are committed through
           ONE vectorized PCS path (layer_proof.commit_boundaries →
           pcs.commit_batch: a single batched NTT + Merkle pass), and
           weight commitments come from a WeightCommitCache so repeated
           queries against the same model skip the ~37 s/layer range-proof
           setup entirely (the paper's amortization);
  stage 3  proving — one ProofJob per selected layer, dispatched over a
           thread-pool worker fleet through ProofWorkReplayQueue
           (runtime/scheduler.py).  Layer proofs are independent given the
           stage-2 commitments, so workers parallelize freely and a lost
           worker's layer is simply re-queued and re-proven.

Proving is Fiat-Shamir deterministic, so the engine's output is
bit-identical across worker counts: ``workers=1`` reproduces the seed's
sequential transcripts exactly, and ``workers>=2`` produces the same
proofs faster.  chain.prove_model is now a thin wrapper over this engine.

Lock order (ranked in repro.analysis.locks): ``ProverEngine._pool_lock``
is rank 30 and ``WeightCommitCache._lock`` rank 40 — both may be taken
under the service lock (rank 20) and may be held while acquiring the
scheduler lock (rank 50) or ``SumcheckRoundBatcher._cv`` (rank 60);
``_cv`` itself only ever wraps rank-70 leaves.
"""
from __future__ import annotations

import dataclasses
import hashlib
import multiprocessing
import threading
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import blocks as B
from repro.core import chain as CH
from repro.core import layer_proof as LP
from repro.core import pcs as PCS
from repro.core import poseidon2 as P2
from repro.core import sumcheck as SC
from repro.kernels import ops as KOPS
from .scheduler import ProofScheduler, ScheduleStats


# ---------------------------------------------------------------------------
# Weight-commitment cache (setup amortization, paper §4: ~37 s/layer setup
# vs ~6 s/layer proving).
# ---------------------------------------------------------------------------
def _weights_digest(cfg: B.BlockCfg, w: Dict[str, np.ndarray],
                    params: PCS.PCSParams) -> bytes:
    h = hashlib.sha256()
    h.update(repr((cfg, params.blowup, params.queries)).encode())
    for k in sorted(w):
        a = np.ascontiguousarray(w[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.digest()


class WeightCommitCache:
    """Cache of WeightCommits keyed by weight root.

    Two levels, both exact:
      * by_root — keyed by the PCS weight root: a fresh commit whose root
        matches a cached entry reuses the cached range proof (skips the
        dominant setup cost);
      * a content-digest fast path (sha256 of the raw weight arrays + cfg
        + PCS params) that skips even the re-commit for the common case of
        serving many queries against the same resident model.

    Thread-safe; counts its hits and misses.
    """

    def __init__(self):
        self._by_digest: Dict[bytes, LP.WeightCommit] = {}
        self._by_root: Dict[bytes, LP.WeightCommit] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._by_root)

    def get_or_setup(self, cfg: B.BlockCfg, w: Dict[str, np.ndarray],
                     params: PCS.PCSParams,
                     name: str = "wt") -> LP.WeightCommit:
        digest = _weights_digest(cfg, w, params)
        with self._lock:
            cached = self._by_digest.get(digest)
        if cached is not None:
            with self._lock:
                self.hits += 1
            return cached
        wt = LP.commit_weights(cfg, w, params, name)
        if wt.root is None:
            return wt
        root_key = (params.blowup, params.queries, wt.root.tobytes())
        with self._lock:
            cached = self._by_root.get(root_key)
        if cached is not None:
            # same published root: reuse the amortized range proof
            with self._lock:
                self.hits += 1
                self._by_digest[digest] = cached
            return cached
        wt.range_tape = LP.weight_range_proof(wt, params, name)
        with self._lock:
            self.misses += 1
            self._by_digest[digest] = wt
            self._by_root[root_key] = wt
        return wt


# ---------------------------------------------------------------------------
# Cross-layer sum-check round batching (fused kernel path, thread backend).
#
# The fused kernel (kernels/sumcheck_round.py) carries the sponge state as a
# (K, 16) operand, so K sum-check claims from *different* layer proofs —
# independent transcripts by construction — can share one launch per round
# index.  This batcher is the rendezvous point: worker threads register for
# the duration of their ProofJob, sumcheck.prove routes their claims here,
# and whichever thread completes a wave (all registered threads have a
# pending claim, or a straggler timeout fires) stacks the same-shape claims
# and runs them through ONE KOPS.sumcheck_prove_rounds call.  Each claim
# still rides its own sponge row, so per-layer transcripts remain
# byte-identical to the sequential reference path.
# ---------------------------------------------------------------------------
class SumcheckRoundBatcher:
    """Coalesces concurrent same-shape sum-check claims into multi-claim
    fused kernel launches.  Installed via ``sumcheck.set_round_batcher``
    by ``ProverEngine.prove_layers`` (thread backend, fused path, >1
    worker); threads that never registered bypass it entirely."""

    def __init__(self, timeout: float = 0.05):
        self._cv = threading.Condition()
        self._registered: Set[int] = set()
        self._pending: Dict[int, Tuple[tuple, jnp.ndarray]] = {}
        self._results: Dict[int, tuple] = {}
        self._timeout = timeout

    def register(self) -> None:
        with self._cv:
            self._registered.add(threading.get_ident())

    def deregister(self) -> None:
        with self._cv:
            self._registered.discard(threading.get_ident())
            # a departing thread may be the last hold-out of a wave
            self._cv.notify_all()

    def registered(self) -> bool:
        return threading.get_ident() in self._registered

    def _wave_complete(self) -> bool:
        return self._registered <= set(self._pending)

    def _flush(self) -> None:
        """Run every pending claim, grouped by (d, n) into stacked launches.
        Caller holds the lock."""
        pending, self._pending = self._pending, {}
        groups: Dict[Tuple[int, int], List[int]] = {}
        for ident, (factors, _) in pending.items():
            groups.setdefault(
                (len(factors), factors[0].shape[-2]), []).append(ident)
        for (d, n), idents in groups.items():
            K = len(idents)
            kp = 1 << max(K - 1, 0).bit_length()   # pad: bounded jit keys
            fs = []
            for t in range(d):
                rows = [pending[i][0][t] for i in idents]
                rows += [jnp.zeros((n, 4), jnp.uint32)] * (kp - K)
                fs.append(jnp.stack(rows))
            sts = jnp.stack(
                [pending[i][1] for i in idents]
                + [jnp.zeros((P2.WIDTH,), jnp.uint32)] * (kp - K))
            rp, pts, fins, sts_out = KOPS.sumcheck_prove_rounds(
                tuple(fs), sts)
            rp_np, fin_np = jax.device_get((rp, fins))
            for k, ident in enumerate(idents):
                self._results[ident] = (
                    np.ascontiguousarray(rp_np[k, :, 1:]), pts[k],
                    fin_np[k], sts_out[k])
        self._cv.notify_all()

    def prove(self, factors: tuple, transcript) -> tuple:
        """Entry point called from sumcheck.prove on a registered worker
        thread: submit the claim, wait for the wave, return
        (SumcheckProof, point) with the transcript advanced exactly as the
        direct path would have."""
        me = threading.get_ident()
        with self._cv:
            self._pending[me] = (factors, transcript.state)
            self._cv.notify_all()
            deadline = time.monotonic() + self._timeout
            while me not in self._results:
                if self._wave_complete():
                    self._flush()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:      # straggler guard: launch a partial wave
                    self._flush()
                    continue
                self._cv.wait(remaining)
            rp, pt, fin, st = self._results.pop(me)
        transcript.set_state(st)
        return SC.SumcheckProof(round_polys=rp, final_evals=fin), pt


# ---------------------------------------------------------------------------
# Process-backed proving (true parallelism).
#
# The prover is dispatch-bound at small widths: thousands of tiny jnp ops
# per sum-check round, all serialized by the GIL, so a *thread* fleet alone
# cannot scale layer proving on CPU (measured 0.93x on 2 cores).  The
# "process" backend keeps the thread fleet for claim/complete/requeue
# semantics but delegates each layer proof to a spawned worker process —
# layer proofs are pure functions of picklable inputs (paper §3.3), so
# shipping (cfg, commits, trace) and receiving a LayerProof is all the
# coordination needed.  Workers pay a one-time import+jit warmup; a
# persistent pool amortizes it across queries (the serving steady state).
# ---------------------------------------------------------------------------
def _process_prove_layer(payload):
    (cfg, layer_index, wt, b_in, b_out, trace, params, cir) = payload
    from repro.core import layer_proof as LP_worker
    return LP_worker.prove_layer(cfg, layer_index, wt, b_in, b_out, trace,
                                 params, check_input_range=cir)


# ---------------------------------------------------------------------------
# Engine.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ProofJob:
    """One unit of stage-3 work: prove layer `layer` of the current query."""
    layer: int
    check_input_range: bool


@dataclasses.dataclass
class ForwardTrace:
    """Stage-1 output: boundary activations h_0..h_L + per-layer traces."""
    acts: List[np.ndarray]
    traces: List[Dict[str, np.ndarray]]


@dataclasses.dataclass
class EngineReport:
    """Host seconds of one engine call, from its spans (``repro.spans``):
    the three stages (``total_seconds`` is their sum), the ``PHASES`` of
    its layer proofs summed over every worker thread that proved one, the
    compile wait on those threads, and the layer tapes' ``TAPE_COUNTS``."""
    forward_seconds: float
    commit_seconds: float
    prove_seconds: float
    total_seconds: float
    workers: int
    jobs: int
    claims: int
    losses: int
    phase_seconds: Dict[str, float]
    compile_seconds: float
    tape_counts: Dict[str, int]


@dataclasses.dataclass
class BatchEngineReport(EngineReport):
    """EngineReport for a coalesced multi-query window (``prove_many``):
    ``commit_seconds`` is the ONE shared boundary-commit pass for all
    ``batch_size`` queries."""
    batch_size: int = 1


class ProverEngine:
    """Staged layerwise prover: forward replay → batched commit → parallel
    proof generation.  See module docstring for the stage breakdown."""

    def __init__(self, cfgs: Sequence[B.BlockCfg],
                 weights_raw: Sequence[Dict[str, np.ndarray]],
                 params: PCS.PCSParams,
                 wt_commits: Optional[Sequence[LP.WeightCommit]] = None,
                 weight_cache: Optional[WeightCommitCache] = None,
                 workers: int = 1,
                 fail_claims: Optional[Set[int]] = None,
                 backend: str = "thread"):
        assert len(cfgs) == len(weights_raw)
        assert backend in ("thread", "process")
        if backend == "process" and KOPS.on_tpu():
            # this process holds the chip; a spawned worker would fail or
            # hang trying to open it
            raise RuntimeError(
                "ProverEngine(backend='process') cannot run on a TPU: the "
                "chip belongs to this process, so worker processes cannot "
                "reach it. Use backend='thread'.")
        self.cfgs = list(cfgs)
        self.weights_raw = list(weights_raw)
        self.params = params
        self.workers = max(1, int(workers))
        self.fail_claims = fail_claims
        self.backend = backend
        # explicit None check: an *empty* cache is falsy via __len__
        self.weight_cache = (weight_cache if weight_cache is not None
                             else WeightCommitCache())
        self._wt_commits: Optional[List[LP.WeightCommit]] = (
            list(wt_commits) if wt_commits is not None else None)
        self._pool = None
        self._pool_lock = threading.Lock()

    # -- process-pool lifecycle (backend="process") -------------------------
    def _ensure_pool(self):
        with self._pool_lock:
            if self._pool is None:
                ctx = multiprocessing.get_context("spawn")
                self._pool = ctx.Pool(processes=self.workers)
            return self._pool

    def close(self):
        """Tear down the process pool (no-op for the thread backend)."""
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- stage 0: setup (amortized) -----------------------------------------
    @property
    def wt_commits(self) -> List[LP.WeightCommit]:
        if self._wt_commits is None:
            self._wt_commits = [
                self.weight_cache.get_or_setup(cfg, w, self.params)
                for cfg, w in zip(self.cfgs, self.weights_raw)]
        return self._wt_commits

    # -- stage 1: quantized forward replay ----------------------------------
    def run_forward(self, x0: np.ndarray) -> ForwardTrace:
        h = x0
        acts, traces = [x0], []
        for cfg, w in zip(self.cfgs, self.weights_raw):
            h, tr = B.block_forward(cfg, w, h)
            acts.append(h)
            traces.append(tr)
        return ForwardTrace(acts=acts, traces=traces)

    # -- stage 2: batched boundary commitment -------------------------------
    def _boundary_cfgs(self) -> List[B.BlockCfg]:
        L = len(self.cfgs)
        # boundary l is laid out by the config of the layer that consumes it
        # (its input side); the final boundary keeps the last layer's layout.
        return [self.cfgs[0]] + [self.cfgs[min(l + 1, L - 1)]
                                 for l in range(L)]

    def commit_boundaries(self, fwd: ForwardTrace) -> List[LP.BoundaryCommit]:
        return LP.commit_boundaries(self._boundary_cfgs(), fwd.acts,
                                    self.params)

    def commit_boundaries_coalesced(self, fwds: Sequence[ForwardTrace]
                                    ) -> List[List[LP.BoundaryCommit]]:
        """Stage 2 for MANY queries in one pass (gateway coalescing).

        The boundary activations of every query in the batch ride ONE
        ``layer_proof.commit_boundaries`` call — same-width boundaries
        across queries land in a single ``pcs.commit_batch`` NTT + Merkle
        pass, so a K-query window costs one batched dispatch sequence
        instead of K.  ``commit_batch`` is bit-identical to per-vector
        ``commit``, hence every returned ``BoundaryCommit`` (roots,
        packed ints, trees) equals the serial ``commit_boundaries`` result
        for its query — the coalesced transcripts ARE the serial
        transcripts.
        """
        bnd_cfgs = self._boundary_cfgs()
        n = len(bnd_cfgs)
        all_cfgs: List[B.BlockCfg] = []
        all_acts: List[np.ndarray] = []
        for fwd in fwds:
            all_cfgs += bnd_cfgs
            all_acts += fwd.acts
        flat = LP.commit_boundaries(all_cfgs, all_acts, self.params)
        return [flat[i * n:(i + 1) * n] for i in range(len(fwds))]

    # -- stage 3: parallel layer proving ------------------------------------
    def _run_jobs(self, job_keys: Sequence, payload_fn
                  ) -> Tuple[Dict, ScheduleStats]:
        """Dispatch arbitrary prove-layer jobs over the worker fleet.

        ``job_keys`` are hashable ids (a bare layer index, or a
        ``(query, layer)`` tuple when several admitted queries share the
        fleet); ``payload_fn(key)`` builds the ``_process_prove_layer``
        payload.  Thread backend + fused kernels + a real fleet rendezvous
        the workers' sum-check claims into multi-claim fused launches;
        transcripts are per-claim sponge rows, so results are byte-identical
        with or without the batcher.
        """
        batcher = None
        col = spans.collector()         # this call's, lent to the workers
        if self.backend == "process":
            pool = self._ensure_pool()

            def prove_one(key) -> LP.LayerProof:
                # the claiming thread blocks on its worker process; the
                # queue/requeue protocol is unchanged across backends
                with spans.attach(col):
                    return pool.apply(_process_prove_layer,
                                      (payload_fn(key),))
        else:
            batcher = (SumcheckRoundBatcher()
                       if self.workers > 1 and KOPS.use_fused() else None)

            def prove_one(key) -> LP.LayerProof:
                with spans.attach(col):
                    if batcher is None:
                        return _process_prove_layer(payload_fn(key))
                    batcher.register()
                    try:
                        return _process_prove_layer(payload_fn(key))
                    finally:
                        batcher.deregister()

        sched = ProofScheduler(workers=self.workers,
                               fail_claims=self.fail_claims)
        if batcher is not None:
            # additive install: concurrent proves (each with its own
            # batcher) coexist — a worker thread is routed to the one
            # batcher it registered with.
            SC.add_round_batcher(batcher)
            try:
                return sched.run(list(job_keys), prove_one)
            finally:
                SC.remove_round_batcher(batcher)
        return sched.run(list(job_keys), prove_one)

    def prove_layers(self, jobs: Sequence[ProofJob],
                     boundaries: List[LP.BoundaryCommit],
                     fwd: ForwardTrace
                     ) -> Tuple[Dict[int, LP.LayerProof], ScheduleStats]:
        by_layer = {j.layer: j for j in jobs}

        def payload(l: int):
            job = by_layer[l]
            return (self.cfgs[l], l, self.wt_commits[l], boundaries[l],
                    boundaries[l + 1], fwd.traces[l], self.params,
                    job.check_input_range)

        return self._run_jobs([j.layer for j in jobs], payload)

    # -- full pipeline ------------------------------------------------------
    def prove(self, x0: np.ndarray,
              layer_subset: Optional[Sequence[int]] = None
              ) -> Tuple[CH.ModelProof, EngineReport]:
        wt_commits = self.wt_commits          # setup (cached/amortized)
        with spans.collect() as col:
            with spans.span("engine.forward"):
                fwd = self.run_forward(x0)
            with spans.span("engine.commit"):
                boundaries = self.commit_boundaries(fwd)
            subset = list(range(len(self.cfgs)) if layer_subset is None
                          else layer_subset)
            jobs = [ProofJob(layer=l, check_input_range=(l == 0))
                    for l in subset]
            with spans.span("engine.prove"):
                done, stats = self.prove_layers(jobs, boundaries, fwd)
        proof = CH.ModelProof(
            layer_proofs=[done[l] for l in subset],
            boundary_roots=[b.root for b in boundaries],
            wt_roots=[w.root for w in wt_commits])
        return proof, _report(EngineReport, col, stats)

    def prove_many(self, x0s: Sequence[np.ndarray],
                   layer_subsets: Optional[Sequence[Sequence[int]]] = None
                   ) -> Tuple[List[CH.ModelProof], BatchEngineReport]:
        """Prove a WINDOW of queries with coalesced stage-2 commits.

        All queries' boundary activations go through ONE batched
        NTT/Merkle pass (``commit_boundaries_coalesced``) and every
        ``(query, layer)`` proof job drains the SAME worker fleet in one
        scheduler run — the gateway's cross-query coalescing point.
        Fiat-Shamir determinism + the bit-identical batched commit mean
        each returned ``ModelProof`` equals the one ``prove`` would have
        produced for its query alone.
        """
        K = len(x0s)
        wt_commits = self.wt_commits          # setup (cached/amortized)
        if layer_subsets is None:
            layer_subsets = [list(range(len(self.cfgs)))] * K
        subsets = [list(s) for s in layer_subsets]
        assert len(subsets) == K
        with spans.collect() as col:
            with spans.span("engine.forward"):
                fwds = [self.run_forward(np.asarray(x)) for x in x0s]
            with spans.span("engine.commit"):
                per_query_bounds = self.commit_boundaries_coalesced(fwds)

            def payload(key):
                qi, l = key
                return (self.cfgs[l], l, wt_commits[l],
                        per_query_bounds[qi][l], per_query_bounds[qi][l + 1],
                        fwds[qi].traces[l], self.params, l == 0)

            job_keys = [(qi, l) for qi, sub in enumerate(subsets)
                        for l in sub]
            with spans.span("engine.prove"):
                done, stats = self._run_jobs(job_keys, payload)
        proofs = [
            CH.ModelProof(
                layer_proofs=[done[(qi, l)] for l in subsets[qi]],
                boundary_roots=[b.root for b in per_query_bounds[qi]],
                wt_roots=[w.root for w in wt_commits])
            for qi in range(K)]
        return proofs, _report(BatchEngineReport, col, stats, batch_size=K)


# Imported here, below the class: a Pallas kernel traced during the weight
# set-up or a boundary commit carries the source lines of the methods
# above in its compile-cache key.
from repro import spans  # noqa: E402

#: the phases of a layer proof, each a span ``layer.<phase>`` in
#: ``layer_proof.prove_layer``
PHASES = ("witness", "argument", "lookups", "openings")
#: what ``layer_proof.tape_counts`` counts on each finished layer tape,
#: each a count ``tape.<name>``
TAPE_COUNTS = ("sumchecks", "vals")


def _report(report_cls, col: spans.Collector, stats: ScheduleStats,
            **kw) -> EngineReport:
    stage = [col.seconds(f"engine.{s}") for s in ("forward", "commit",
                                                    "prove")]
    return report_cls(
        forward_seconds=stage[0], commit_seconds=stage[1],
        prove_seconds=stage[2], total_seconds=sum(stage),
        workers=stats.workers, jobs=stats.jobs, claims=stats.claims,
        losses=stats.losses,
        phase_seconds={p: col.seconds(f"layer.{p}") for p in PHASES},
        compile_seconds=col.seconds(spans.COMPILE),
        tape_counts={c: col.count(f"tape.{c}") for c in TAPE_COUNTS}, **kw)
