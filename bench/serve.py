"""The measured window: a gateway over the prover, closed-loop clients.

``Window(root, config, traffic, seed).run(seconds, trace, t_process)``
builds the service from the seed, warms it up, measures one window and
returns the run's record: one entry per window query (send and done
times, wire bytes), the gateway's metric snapshots at the window's two
ends, the boundary activations the prover's forward replay produced for
each query, compile counts, the device's peak memory, and with
``trace`` the reduced profiler trace of one slice of the window
(``TraceSlice``).  Nothing here decides ``correct``
(``check.py``) or computes a metric (``metrics/``).
"""
from __future__ import annotations

import contextlib
import glob
import hashlib
import os
import shutil
import sys
import threading
import time
from typing import Dict, List

import numpy as np

import model
import trace_reduce
from sampler import StackSampler

CLIENT_TIMEOUT = 1500.0       # a cold first query compiles for minutes
TRACE_SECONDS = 3.0           # the traced slice of a --trace 1 window
WARMUP_INDEX = 1 << 20        # warm-up queries: a stream no window reaches


def forward_key(q: np.ndarray) -> bytes:
    """The query's identity in the record of forward replays."""
    return hashlib.sha256(np.ascontiguousarray(q, np.int64).tobytes()).digest()


class CompileCounter:
    """JAX's compile events: programs built (compiled, or loaded from the
    persistent cache), of those loaded, and the seconds spent building,
    summed over the threads that build."""

    def __init__(self):
        import jax
        self.built = self.loaded = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def _duration(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.built += 1
            self.seconds += secs

    @property
    def compiled(self) -> int:
        return self.built - self.loaded


@contextlib.contextmanager
def on_host():
    """The client's side: JAX work on the host CPU, on the reference
    kernel path (no kernel runs there)."""
    import jax
    from repro.kernels import ops as KOPS
    with jax.default_device(jax.devices("cpu")[0]), KOPS.thread_path("ref"):
        yield


def host_roots(cfg, config: dict, pol, acts: list) -> list:
    """Roots of the reference kernel path's commitments to the boundary
    activations ``acts``, computed on the host CPU."""
    from repro.core import layer_proof as LP
    from repro.core import pcs as PCS
    params = PCS.PCSParams(blowup=config["pcs_blowup"],
                           queries=pol.pcs_queries)
    with on_host():
        coms = LP.commit_boundaries([cfg] * len(acts), acts, params)
        return [np.asarray(c.root) for c in coms]


def block_cfg(config: dict):
    from repro.core import blocks as B
    b = config["block"]
    return B.BlockCfg(family=config["family"], d=b["d"], dff=b["dff"],
                      heads=b["heads"], kv_heads=b["heads"], dh=b["dh"],
                      seq=b["seq"])


def policy(config: dict, traffic: dict):
    from repro import api
    return api.VerifyPolicy(**{"pcs_queries": config["pcs_queries"],
                               **traffic.get("policy", {})})


class TraceSlice:
    """The traced part of a ``--trace 1`` window: the first
    ``TRACE_SECONDS`` of its second round (a round is every client's
    query of one index; with several clients, a coalesced batch), the
    same phase of an attestation in every run.  The profiler starts once
    every client's first query has completed; a client that completes
    its first waits for that, so all of the round's queries are sent
    under the trace.  It stops ``TRACE_SECONDS`` later, or when a query
    of the round completes if that comes first.

    Neither a whole attestation nor its end is traced, because of what
    stopping the profiler costs on the chip: after a whole d=768
    attestation (10.3 s traced) it took 221 s, after the last 4 s of one
    191 s, and those runs 504 s and 479 s, more than a run may last.
    Device ops only: the host's activity comes from the stack sampler,
    and host events would double the trace; off the chip the XLA:CPU
    executors, which stand in for the device, are host events.  The
    loaded programs' HLO is left out of the trace: on the chip it cost
    about 50 s a run."""

    def __init__(self, n_clients: int, trace_dir: str, package_dir: str):
        self.clients = set(range(n_clients))
        self.first = set()            # clients whose first query completed
        self.dir = trace_dir
        self.lock = threading.Lock()
        self.started = threading.Event()
        self.sampler = StackSampler(package_dir,
                                    skip=("bench-client", "bench-sampler"))
        self.timer = None
        self.wall = None                # host wall clock (ns): start, stop
        self.stop_s = 0.0               # seconds the profiler took to stop
        self._start_ns = None

    def after(self, client: int, index) -> None:
        """Client ``client`` completed its query ``index``; ``None`` when
        its loop ended."""
        with self.lock:
            if index is None:
                self.clients.discard(client)
            elif index == 0:
                self.first.add(client)
            if self.wall is None and self._start_ns is None \
                    and not self.started.is_set():
                if self.clients and self.clients <= self.first:
                    self._start()
                elif not self.clients:
                    self.started.set()
            elif self._start_ns is not None and (not self.clients or (
                    index is not None and index >= 1)):
                self._stop()
        if index == 0:
            self.started.wait()

    def _start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.enable_hlo_proto = False
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0 if jax.default_backend() == "tpu" else 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.sampler.__enter__()
        self._start_ns = time.time_ns()
        self.timer = threading.Timer(TRACE_SECONDS, self._timeout)
        self.timer.start()
        self.started.set()

    def _timeout(self) -> None:
        with self.lock:
            if self._start_ns is not None:
                self._stop()

    def _stop(self) -> None:
        import jax
        self.wall = (self._start_ns, time.time_ns())
        self._start_ns = None
        self.sampler.__exit__(None, None, None)
        t = time.monotonic()
        jax.profiler.stop_trace()
        self.stop_s = time.monotonic() - t

    def close(self) -> None:
        """After the window: a trace still running is stopped."""
        self.started.set()
        if self.timer is not None:
            self.timer.cancel()
            self.timer.join()
        with self.lock:
            if self._start_ns is not None:
                self._stop()

    def reduce(self) -> dict:
        """The reduced trace of the slice; empty when none was taken."""
        if self.wall is None:
            return {}
        pb = sorted(glob.glob(os.path.join(self.dir, "plugins", "profile",
                                           "*", "*.xplane.pb")))
        pd = trace_reduce.load(pb[-1])
        zero = trace_reduce.profile_start(pd)
        out = trace_reduce.reduce(
            pd, (self.wall[0] - zero, self.wall[1] - zero),
            [(t - zero, lab) for t, lab in self.sampler.samples])
        del pd
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


class Window:
    def __init__(self, root: str, config: dict, traffic: dict, seed: int,
                 log=print):
        self.root, self.config, self.traffic = root, config, traffic
        self.seed, self.log = seed, log
        self.forwards: Dict[bytes, List[np.ndarray]] = {}

    # -- the program's forward replay, recorded as it runs ------------------
    def _record_forward(self, engine) -> None:
        inner = engine.run_forward

        def run_forward(x0):
            fwd = inner(x0)
            self.forwards[forward_key(x0)] = [np.array(a) for a in fwd.acts]
            return fwd
        engine.run_forward = run_forward

    def _clients(self, address, n: int, tag: str):
        from repro.gateway import GatewayClient
        return [GatewayClient(*address, client_id=f"{tag}-{c}",
                              timeout=CLIENT_TIMEOUT) for c in range(n)]

    def _attest_all(self, clients, queries, pol) -> list:
        """One concurrent request per client; (wire, info) each."""
        out = [None] * len(clients)

        def one(c):
            out[c] = clients[c].attest_bytes(queries[c], pol)
        threads = [threading.Thread(target=one, args=(c,))
                   for c in range(len(clients))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if any(o is None for o in out):
            raise RuntimeError("a warm-up attestation failed")
        return out

    def run(self, seconds: float, trace: bool, t_process: float) -> dict:
        import jax
        sys.path.insert(0, os.path.join(self.root, "src"))
        import repro  # noqa: F401 — importing it sets the persistent cache
        from repro import api
        from repro.gateway import AttestationGateway, GatewayConfig
        # The program's compile-cache policy holds: it caches what takes
        # 0.3 s or more to compile and sets no size, so no entry is
        # evicted.  An environment that sets a size turns eviction on;
        # then every read and write takes a file lock, and the prover's
        # concurrent compiles time out on it (10 s each) and skip the
        # cache.  The program's own setting is restored here.
        jax.config.update("jax_compilation_cache_max_size", -1)
        compiles = CompileCounter()
        cfg, traffic, config = block_cfg(self.config), self.traffic, \
            self.config
        n_clients = int(traffic["clients"])
        if traffic.get("loop", "closed") != "closed":
            raise ValueError(f"traffic loop {traffic['loop']!r}: only closed "
                             "loops are generated")
        pol = policy(config, traffic)
        weights = model.weights(config, self.seed)
        svc = api.ProofService([cfg] * config["layers"], weights,
                               pcs_blowup=config["pcs_blowup"],
                               default_queries=config["pcs_queries"],
                               workers=traffic["service"]["workers"],
                               name=config["name"])
        gw = None
        try:
            card = svc.model_card          # weight commitment + range proof
            self.log(f"model card at +{time.monotonic() - t_process:.1f}s; "
                     f"{compiles.compiled} compiled, {compiles.loaded} "
                     "loaded from the cache")
            self._record_forward(svc.engine_for(pol.pcs_queries))
            # the checks' host commitments compile beside the device's
            # warm-up, on cores the prover leaves idle there
            zeros = [np.zeros_like(model.query(config, self.seed, 0, 0))
                     ] * (config["layers"] + 1)
            host_warm = threading.Thread(
                target=host_roots, args=(cfg, config, pol, zeros))
            host_warm.start()
            gw = AttestationGateway(svc, GatewayConfig(**traffic["gateway"]))
            address = gw.serve(port=0, result_timeout=CLIENT_TIMEOUT).address
            warm = self._clients(address, n_clients, "warm")
            try:
                got = self._attest_all(
                    warm, [model.query(config, self.seed, c, WARMUP_INDEX)
                           for c in range(n_clients)], pol)
            finally:
                for c in warm:
                    c.close()
            with on_host():
                rep = api.verify(got[0][0], model.query(
                    config, self.seed, 0, WARMUP_INDEX), card, pol)
            if not rep.ok:
                raise RuntimeError(f"warm-up attestation rejected: "
                                   f"{rep.reason}")
            host_warm.join()
            rec = self._window(gw, address, seconds, trace, pol, compiles)
            rec["setup_s"] = rec.pop("t0") - t_process
            stats = jax.devices()[0].memory_stats() or {}
            rec["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        finally:
            if gw is not None:
                gw.close()
            svc.close()
        rec.update(card=card, policy=pol, config=config, traffic=traffic,
                   forwards=self.forwards, block_cfg=cfg)
        return rec

    def _window(self, gw, address, seconds, trace, pol, compiles) -> dict:
        import repro
        n = int(self.traffic["clients"])
        clients = self._clients(address, n, "bench-client")
        queries: List[dict] = []
        lock = threading.Lock()
        state = {}
        traced = None

        def loop(c: int) -> None:
            i = 0
            try:
                while True:
                    if i and time.monotonic() >= state["deadline"]:
                        return
                    q = model.query(self.config, self.seed, c, i)
                    entry = {"client": c, "index": i, "query": q,
                             "sent": time.monotonic()}
                    try:
                        wire, info = clients[c].attest_bytes(q, pol)
                        entry.update(wire=wire,
                                     batch_size=info.get("batch_size"))
                    except Exception as e:  # noqa: BLE001 — counted as failed
                        entry["error"] = f"{type(e).__name__}: {e}"
                    entry["done"] = time.monotonic()
                    with lock:
                        queries.append(entry)
                    if "error" in entry:
                        return
                    if traced is not None:
                        traced.after(c, i)
                    i += 1
            finally:
                if traced is not None:
                    traced.after(c, None)

        threads = [threading.Thread(target=loop, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(n)]
        snap0 = gw.metrics_snapshot()
        c0, s0 = compiles.built, compiles.seconds
        t0 = time.monotonic()
        state["deadline"] = t0 + seconds
        if trace:
            traced = TraceSlice(n, os.path.join(self.root, ".bench_out",
                                                "trace"),
                                os.path.dirname(repro.__file__))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if traced is not None:
            traced.close()
        t1 = max(q["done"] for q in queries)
        snap1 = gw.metrics_snapshot()
        in_window = compiles.built - c0
        self.log(f"window: {len(queries)} queries in {t1 - t0:.3f}s; "
                 f"{in_window} programs compiled or loaded inside it, "
                 f"{compiles.seconds - s0:.2f}s of building summed over "
                 "threads")
        for c in clients:
            c.close()
        reduced = {}
        if traced is not None:
            t = time.monotonic()
            reduced = traced.reduce()
            self.log(f"trace: {reduced.get('window_s', 0):.3f}s traced, the "
                     f"profiler stopped in {traced.stop_s:.1f}s, read in "
                     f"{time.monotonic() - t:.1f}s")
        queries.sort(key=lambda q: (q["sent"], q["client"]))
        return {"t0": t0, "window_s": t1 - t0, "queries": queries,
                "attempted": len(queries), "gateway_before": snap0,
                "gateway_after": snap1, "compiles_in_window": in_window,
                "trace": reduced}
