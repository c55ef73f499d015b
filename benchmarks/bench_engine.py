"""Prover-engine throughput: sequential vs parallel layerwise proving.

The paper's §3.3 claim is that layerwise decomposition *enables parallel
proving*; this benchmark measures it on a >=4-layer chain.  Both runs go
through the identical staged ProverEngine — only the worker count of the
stage-3 proof fleet differs — and Fiat-Shamir determinism means the
parallel run's transcripts are bit-identical to the sequential ones
(asserted here).  A final scenario drives N queries through ONE resident
``api.ProofService`` (process backend) and reports cold-vs-warm
queries/sec: the cold query pays worker spawn + jit + weight range-proof
setup, the warm ones ride the resident fleet and WeightCommitCache.
Results land in BENCH_engine.json at the repo root:

    PYTHONPATH=src python benchmarks/bench_engine.py [--ci]
"""
import argparse
import json
import os
import pickle
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run(ci: bool = True, layers: int = 4, workers: int = None,
        queries: int = 4, out: str = None):
    if workers is None:
        workers = min(4, max(2, os.cpu_count() or 2))
    from repro.core import blocks as B
    from repro.core import pcs as PCS
    from repro.kernels import ops as KOPS
    from repro.runtime.engine import ProverEngine, WeightCommitCache

    d, heads = (16, 2) if ci else (32, 4)
    cfg = B.BlockCfg(family="gpt2", d=d, dff=4 * d, heads=heads,
                     kv_heads=heads, dh=d // heads, seq=8)
    params = PCS.PCSParams(blowup=4, queries=queries)
    rng = np.random.default_rng(0)
    weights = [B.init_weights(cfg, rng) for _ in range(layers)]
    x0 = np.clip(np.round(rng.normal(0, 0.5,
                                     (cfg.d_pad, cfg.seq)) * 256),
                 -32768, 32767).astype(np.int64)
    cache = WeightCommitCache()
    cfgs = [cfg] * layers

    print(f"setup: {layers} layers, d={d}, queries={queries} "
          "(weight commits + range proofs, cached)...", flush=True)
    t0 = time.time()
    warm = ProverEngine(cfgs, weights, params, weight_cache=cache,
                        workers=1)
    _ = warm.wt_commits
    # warm the jit caches so neither timed run pays compilation
    warm.prove(x0, layer_subset=[0])
    t_setup = time.time() - t0
    print(f"setup+warmup in {t_setup:.1f}s", flush=True)

    results = {}
    proofs = {}
    # a chip belongs to one process: on a TPU the process-backend
    # sections are skipped (the engine refuses to spawn there)
    fleet = not KOPS.on_tpu()
    runs = (("sequential", 1, "thread"),
            ("parallel_threads", workers, "thread"))
    if fleet:
        runs += (("sequential_fleet", 1, "process"),
                 ("parallel", workers, "process"))
    for label, n_workers, backend in runs:
        eng = ProverEngine(cfgs, weights, params, weight_cache=cache,
                           workers=n_workers, backend=backend)
        if backend == "process":
            # warm the fleet untimed: spawned workers pay import + jit
            # once, then stay resident (the serving steady state)
            eng.prove(x0)
        t0 = time.time()
        proof, report = eng.prove(x0)
        wall = time.time() - t0
        eng.close()
        proofs[label] = proof
        results[label] = {
            "workers": n_workers,
            "backend": backend,
            "wall_seconds": wall,
            "prove_seconds": report.prove_seconds,
            "commit_seconds": report.commit_seconds,
            "forward_seconds": report.forward_seconds,
            "proofs_per_sec": layers / report.prove_seconds,
            "claims": report.claims,
        }
        print(f"{label} ({n_workers} {backend} workers): {wall:.1f}s wall, "
              f"{layers / report.prove_seconds:.3f} layer proofs/sec",
              flush=True)

    identical = all(
        pickle.dumps(a.tape) == pickle.dumps(p.layer_proofs[i].tape)
        for p in proofs.values()
        for i, a in enumerate(proofs["sequential"].layer_proofs))

    # -- kernel-path comparison: the SAME in-process sequential prove, ref
    # (pure-jnp oracle) vs fused (Pallas kernel path), warm in both cases.
    # Transcript equality across paths is asserted — the fused path is
    # only admissible because it is byte-identical to the oracle.
    kernel_results = {}
    ambient = os.environ.get("NANOZK_KERNEL_PATH")
    try:
        for path in ("ref", "fused"):
            os.environ["NANOZK_KERNEL_PATH"] = path
            eng = ProverEngine(cfgs, weights, params, weight_cache=cache,
                               workers=1)
            eng.prove(x0)                 # untimed: per-path jit warmup
            t0 = time.time()
            proof, report = eng.prove(x0)
            wall = time.time() - t0
            kernel_results[path] = {
                "wall_seconds": wall,
                "prove_seconds": report.prove_seconds,
                "proofs_per_sec": layers / report.prove_seconds,
                "identical_to_ref_transcripts":
                    pickle.dumps([lp.tape for lp in proof.layer_proofs])
                    == pickle.dumps([lp.tape for lp in
                                     proofs["sequential"].layer_proofs]),
            }
            print(f"kernel path {path}: {wall:.1f}s wall, "
                  f"{layers / report.prove_seconds:.3f} layer proofs/sec "
                  "(transcripts identical: "
                  f"{kernel_results[path]['identical_to_ref_transcripts']})",
                  flush=True)
    finally:
        if ambient is None:
            os.environ.pop("NANOZK_KERNEL_PATH", None)
        else:
            os.environ["NANOZK_KERNEL_PATH"] = ambient

    from repro import api
    policy = api.VerifyPolicy(pcs_queries=queries)
    speedup = speedup_vs_inprocess = None
    if fleet:
        # -- warm-service scenario: N queries through ONE resident
        # ProofService (the persistent serving daemon: engine + process
        # fleet + weight cache stay resident, so query 1 pays
        # spawn/jit/setup and the rest don't).
        n_service_queries = 3
        service_rng = np.random.default_rng(1)
        svc_queries = [
            np.clip(np.round(service_rng.normal(0, 0.5,
                                                (cfg.d_pad, cfg.seq)) * 256),
                    -32768, 32767).astype(np.int64)
            for _ in range(n_service_queries)]
        with api.ProofService(cfgs, weights, default_queries=queries,
                              workers=workers, backend="process") as svc:
            t0 = time.time()
            att0 = svc.attest(svc_queries[0], policy)
            t_cold = time.time() - t0   # spawn + jit warmup + first query
            t0 = time.time()
            for q in svc_queries[1:]:
                svc.attest(q, policy)
            t_warm = (time.time() - t0) / (n_service_queries - 1)
        wire_v2 = len(att0.to_bytes(2))   # framed + deduplicated (default)
        wire_v1 = len(att0.to_bytes(1))   # legacy envelope, inline paths
        n_proved = max(1, len(att0.proved_layers))
        results["service"] = {
            "backend": "process",
            "workers": workers,
            "n_queries": n_service_queries,
            "cold_first_query_seconds": t_cold,
            "warm_seconds_per_query": t_warm,
            "cold_queries_per_sec": 1.0 / t_cold,
            "warm_queries_per_sec": 1.0 / t_warm,
            "cold_over_warm": t_cold / t_warm,
            "attestation_wire_bytes": wire_v2,
            "attestation_wire_bytes_v1": wire_v1,
            "wire_kb_per_layer": wire_v2 / n_proved / 1024,
            "wire_kb_per_layer_v1": wire_v1 / n_proved / 1024,
        }
        print(f"attestation wire: v2 {wire_v2 / n_proved / 1024:.1f} "
              f"KB/layer (v1 envelope {wire_v1 / n_proved / 1024:.1f} "
              "KB/layer)", flush=True)
        print(f"resident ProofService ({workers} process workers): cold "
              f"{t_cold:.1f}s/query -> warm {t_warm:.1f}s/query "
              f"({t_cold / t_warm:.2f}x, {1.0 / t_warm:.3f} queries/sec "
              "warm)", flush=True)
        # headline: wall-clock scaling of the proving fleet (1 -> N
        # workers, same process-backed architecture).  Also report
        # parallel vs the in-process sequential loop — on a box this small
        # (cpu_count cores) the in-process prover already soaks up the
        # idle core via XLA intra-op threads, so that ratio is
        # hardware-capped near 1.
        speedup = (results["sequential_fleet"]["prove_seconds"]
                   / results["parallel"]["prove_seconds"])
        speedup_vs_inprocess = (results["sequential"]["prove_seconds"]
                                / results["parallel"]["prove_seconds"])
        print(f"fleet scaling 1->{workers} workers: {speedup:.2f}x "
              f"(vs in-process sequential: {speedup_vs_inprocess:.2f}x), "
              f"identical transcripts: {identical}", flush=True)

    # -- gateway scenario: N concurrent clients through the
    # AttestationGateway.  Round 1 is cold (fresh service: jit + weight
    # setup ride the first window); round 2 is warm.  The dispatcher
    # coalesces each round into ONE window, so all N queries share one
    # batched boundary-commit pass — the per-query commit cost drop vs
    # the serial path is the headline number.
    from repro.gateway import AttestationGateway, GatewayConfig
    from repro.gateway.metrics import merge_batch_sizes
    n_gw = 4
    gw_rng = np.random.default_rng(2)
    gw_queries = [
        np.clip(np.round(gw_rng.normal(0, 0.5,
                                       (cfg.d_pad, cfg.seq)) * 256),
                -32768, 32767).astype(np.int64)
        for _ in range(n_gw)]

    def gw_round(gw):
        tickets = []
        t0 = time.time()
        for i, q in enumerate(gw_queries):
            tickets.append(gw.submit(q, policy, client_id=f"bench-{i}"))
        for t in tickets:
            t.result(timeout=3600)
        return time.time() - t0

    gw_svc = api.ProofService(cfgs, weights, default_queries=queries,
                              workers=workers)
    gw_cfg = GatewayConfig(max_batch=n_gw, window_seconds=0.5,
                           per_client_inflight=n_gw)
    with gw_svc, AttestationGateway(gw_svc, gw_cfg) as gw:
        wall_cold = gw_round(gw)           # jit + weight setup in window 1
        commit_cold = gw_svc.last_report.commit_seconds
        wall_warm = gw_round(gw)
        rep_warm = gw_svc.last_report
        # serial warm baseline on the SAME resident service: per-query
        # commit passes instead of one coalesced pass
        t0 = time.time()
        serial_commit = 0.0
        for q in gw_queries:
            gw_svc.attest(q, policy)
            serial_commit += gw_svc.last_report.commit_seconds
        wall_serial = time.time() - t0
        snap = gw.metrics_snapshot()
    commit_warm = rep_warm.commit_seconds  # the ONE shared pass, window 2
    amort = (serial_commit / n_gw) / max(commit_warm / n_gw, 1e-9)
    results["gateway"] = {
        "clients": n_gw,
        "coalesce_window_batch": rep_warm.batch_size,
        "cold_window_wall_seconds": wall_cold,
        "cold_queries_per_sec": n_gw / wall_cold,
        "warm_window_wall_seconds": wall_warm,
        "warm_queries_per_sec": n_gw / wall_warm,
        "serial_warm_wall_seconds": wall_serial,
        "serial_warm_queries_per_sec": n_gw / wall_serial,
        "commit_seconds_coalesced_window": commit_warm,
        "commit_seconds_coalesced_window_cold": commit_cold,
        "commit_seconds_per_query_coalesced": commit_warm / n_gw,
        "commit_seconds_per_query_serial": serial_commit / n_gw,
        "commit_amortization": amort,
        "coalesce_batch_sizes": merge_batch_sizes(snap),
        "metrics": snap,
    }
    print(f"gateway ({n_gw} concurrent clients, coalesced windows of "
          f"{rep_warm.batch_size}): cold {n_gw / wall_cold:.3f} q/s -> "
          f"warm {n_gw / wall_warm:.3f} q/s (serial warm "
          f"{n_gw / wall_serial:.3f} q/s); per-query commit "
          f"{serial_commit / n_gw:.3f}s serial -> "
          f"{commit_warm / n_gw:.3f}s coalesced ({amort:.2f}x)",
          flush=True)

    report = {
        "config": {"layers": layers, "d": d, "heads": heads, "seq": 8,
                   "pcs_queries": queries, "ci": ci,
                   "cpu_cores": os.cpu_count(),
                   "kernel_path": KOPS.kernel_path()},
        "setup_warmup_seconds": t_setup,
        "kernel_paths": kernel_results,
        "sequential": results["sequential"],
        "parallel_threads": results["parallel_threads"],
        "sequential_fleet": results.get("sequential_fleet"),
        "parallel": results.get("parallel"),
        "service": results.get("service"),
        "gateway": results["gateway"],
        "speedup": speedup,
        "speedup_vs_inprocess_sequential": speedup_vs_inprocess,
        "identical_transcripts": identical,
        "cache": {"hits": cache.hits, "misses": cache.misses},
        "note": ("speedup = wall-clock fleet scaling of process-backed "
                 "parallel proving (1 vs N workers). Thread workers "
                 "cannot scale the dispatch-bound prover (GIL); on "
                 "few-core hosts the in-process sequential loop already "
                 "uses idle cores via XLA intra-op threading, capping "
                 "speedup_vs_inprocess_sequential near 1.0."),
    }
    path = out or os.path.join(ROOT, "BENCH_engine.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {os.path.abspath(path)}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ci", action="store_true",
                    help="small widths/query counts (CI sizes)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--workers", type=int, default=None,
                    help="prover fleet size (default: min(4, cpu_count))")
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    run(ci=args.ci, layers=args.layers, workers=args.workers,
        queries=args.queries, out=args.out)


if __name__ == "__main__":
    main()
