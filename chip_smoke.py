"""Smoke run of the attestation path on one TPU chip.

    python chip_smoke.py

Drives the prover's main path once through the entry points a provider
and a client use: a ``ProofService`` behind an ``AttestationGateway`` on a
localhost socket, and a ``GatewayClient`` that stream-verifies every
attestation it receives.  The block is GPT-2 small at its published
widths (d=768, dff=3072, 12 heads of 64; ``configs/gpt2_small.py``), one
layer deep, seq 8 (as ``benchmarks/table3_block_proof.py``), with random
quantized weights from a fixed seed.  The client verifies on the host
CPU, as clients do.  Alongside the serving, an oracle check attests one
query at the paper's d=128 under the Pallas kernel path and, in another
thread, under the jnp reference path, and requires identical wire
bytes: the oracle contract, checked on the chip's own compiler.

Exits non-zero and prints no result when JAX finds no TPU, or when any
phase fails.  The lines before the last are smoke timings on the device
they name, not benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import concurrent.futures
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
N_QUERIES = 3
PCS_QUERIES = 16                 # the service and table3 default
CLIENT_TIMEOUT = 1200.0          # the first query pays the compiles


def gpt2_block(d: int, heads: int, dh: int, dff: int, seq: int = 8):
    from repro.core import blocks as B
    return B.BlockCfg(family="gpt2", d=d, dff=dff, heads=heads,
                      kv_heads=heads, dh=dh, seq=seq)


@contextlib.contextmanager
def on_host():
    """Run the block's JAX work on the host CPU, where clients verify.
    No kernel runs there, so the client's own commitment to its query
    takes the reference path."""
    import jax
    from repro.kernels import ops as KOPS
    with jax.default_device(jax.devices("cpu")[0]), KOPS.thread_path("ref"):
        yield


def _query(cfg, rng) -> np.ndarray:
    return np.clip(np.round(rng.normal(0, 0.5, (cfg.d_pad, cfg.seq)) * 256),
                   -32768, 32767).astype(np.int64)


def serve_phase(cfg, n_queries: int, pcs_queries: int, log) -> dict:
    """Attest ``n_queries`` queries through the gateway's socket transport
    on the platform's default kernel path; every attestation must
    stream-verify with ``report.ok``."""
    from repro import api
    from repro.core import blocks as B
    from repro.gateway import AttestationGateway, GatewayClient, GatewayConfig
    from repro.kernels import ops as KOPS

    rng = np.random.default_rng(SEED)
    weights = [B.init_weights(cfg, rng)]
    queries = [_query(cfg, rng) for _ in range(n_queries)]
    policy = api.VerifyPolicy(pcs_queries=pcs_queries)
    path = KOPS.kernel_path()
    t0 = time.monotonic()
    log(f"d={cfg.d} setup starts")
    with api.ProofService([cfg], weights, default_queries=pcs_queries,
                          workers=1, name="chip-smoke") as svc:
        card = svc.model_card              # weight commit + range proof
        log(f"d={cfg.d} setup (weight commit + range proof): "
            f"{time.monotonic() - t0:.3f}s")
        with AttestationGateway(svc, GatewayConfig(max_batch=1)) as gw:
            host, port = gw.serve(port=0).address
            for i, q in enumerate(queries):
                t = time.monotonic()
                with GatewayClient(host, port, client_id=f"smoke-{i}",
                                   timeout=CLIENT_TIMEOUT) as client, \
                        on_host():
                    rep = client.attest_verify(q, card, policy)
                wall = time.monotonic() - t
                if not rep.ok:
                    raise AssertionError(
                        f"d={cfg.d} attestation {i} rejected: {rep.reason}")
                er = svc.last_report
                log(f"d={cfg.d} query {i}: round trip {wall:.3f}s, forward "
                    f"{er.forward_seconds:.3f}s, commit "
                    f"{er.commit_seconds:.3f}s, prove "
                    f"{er.prove_seconds:.3f}s, stream verify "
                    f"{rep.verify_seconds:.3f}s, wire "
                    f"{rep.attestation_bytes / 1024:.1f} KB/layer")
    log(f"d={cfg.d}: {n_queries}/{n_queries} gateway attestations verified "
        f"on the {path!r} kernel path")
    return {"kernel_path": path, "verified": n_queries}


def oracle_attestation(cfg, weights, query, pcs_queries: int, log,
                       path=None) -> tuple:
    """(kernel path, wire bytes, model card bytes) of one verified
    attestation of ``query``, proven in the calling thread on ``path``
    (the platform default when None).  Each call runs its own weight
    setup (commitment and range proof), so the roots the attestation
    carries are computed on that path too."""
    from repro import api
    from repro.kernels import ops as KOPS

    with (KOPS.thread_path(path) if path else contextlib.nullcontext()):
        path = KOPS.kernel_path()
        policy = api.VerifyPolicy(pcs_queries=pcs_queries)
        t0 = time.monotonic()
        with api.ProofService([cfg], weights, default_queries=pcs_queries,
                              workers=1, name="chip-smoke-oracle") as svc:
            att = svc.attest(query, policy)
            card = svc.model_card
    att.prove_seconds = 0.0                # wall-clock telemetry
    wire = att.to_bytes(2)
    with on_host():
        rep = api.verify(wire, query, card, policy)
    if not rep.ok:
        raise AssertionError(
            f"d={cfg.d} {path} attestation rejected: {rep.reason}")
    log(f"d={cfg.d} {path} path: setup + attest + verify "
        f"{time.monotonic() - t0:.3f}s, {len(wire)} wire bytes")
    return path, wire, card.to_bytes()


def run_phases(serve_cfg, oracle_cfg, n_queries: int = N_QUERIES,
               pcs_queries: int = PCS_QUERIES, log=print) -> dict:
    """Every phase of the smoke run; raises on the first failure.

    The oracle check attests one ``oracle_cfg`` query on the platform's
    kernel path and again on ``ref``; the two must verify and be
    byte-identical on the wire, and so must their model cards (each
    path runs its own weight setup).  Both attestations run in threads
    of their own while the gateway serves: a cold run is mostly
    compilation, which releases the GIL, so the three provers' compiles
    overlap."""
    from repro.core import blocks as B

    rng = np.random.default_rng(SEED + 1)
    weights = [B.init_weights(oracle_cfg, rng)]
    query = _query(oracle_cfg, rng)
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        first = pool.submit(oracle_attestation, oracle_cfg, weights, query,
                            pcs_queries, log)
        twin = pool.submit(oracle_attestation, oracle_cfg, weights, query,
                           pcs_queries, log, "ref")
        serve = serve_phase(serve_cfg, n_queries, pcs_queries, log)
        path, wire, card = first.result()
        _, ref_wire, ref_card = twin.result()
    if card != ref_card:
        raise AssertionError(f"d={oracle_cfg.d}: the {path!r} path's weight "
                             "commitment differs from the reference path's")
    if wire != ref_wire:
        raise AssertionError(f"d={oracle_cfg.d}: the {path!r} path's "
                             "attestation differs from the reference path's")
    log(f"d={oracle_cfg.d}: {path} and ref model cards and attestations "
        f"byte-identical ({len(wire)} wire bytes)")
    return {"serve": serve,
            "oracle": {"kernel_path": path, "identical_bytes": len(wire)}}


def main() -> int:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    t0 = time.monotonic()

    def log(msg):
        print(f"[smoke on {device['kind']}, +{time.monotonic() - t0:.1f}s] "
              f"{msg}", flush=True)

    out = run_phases(gpt2_block(768, heads=12, dh=64, dff=3072),
                     gpt2_block(128, heads=4, dh=32, dff=512), log=log)
    for phase in ("serve", "oracle"):
        if out[phase]["kernel_path"] != "fused":
            raise AssertionError(
                f"the {phase} phase ran on the "
                f"{out[phase]['kernel_path']!r} path, not the TPU default "
                "'fused'")
    stats = devs[0].memory_stats() or {}
    log(f"device peak bytes in use: {stats.get('peak_bytes_in_use')}; "
        f"total {time.monotonic() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
