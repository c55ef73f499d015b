"""Compile ahead: the programs started in the pool are the ones later run.

On a TPU the prover starts the compiles of every round of a sum-check,
every level of a Merkle tree and every bucket of an opening's e-vector
before it runs the first of them.  That pays only if each later call
finds its executable in JAX's cache, i.e. if the shapes, dtypes and
static arguments handed to the pool are exactly those of the call.  These
tests check that on CPU: every compile of the watched programs happens in
a pool thread, none in the caller's.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import field as F
from repro.core import merkle as M
from repro.core import pcs as PCS
from repro.core import sumcheck as SC
from repro.core.transcript import Transcript
from repro.kernels import ahead as AH
from repro.kernels import ops as KOPS
from repro.kernels import poseidon2_kernel as PK
from repro.kernels import sumcheck_round as SR

_COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def compiles():
    """(thread name, program name) of every backend compile in the test;
    in-memory caches are cleared first, so nothing is compiled already."""
    jax.clear_caches()
    seen = []

    def listener(event, _secs, **kw):
        if event == _COMPILE:
            seen.append((threading.current_thread().name,
                         kw.get("fun_name", "")))

    jax.monitoring.register_event_duration_secs_listener(listener)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listener)


def _split(seen, *names):
    """(pool compiles, caller compiles) of the named programs."""
    hits = [(t, f) for t, f in seen if any(n in f for n in names)]
    pool = [f for t, f in hits if t.startswith("compile-ahead")]
    return pool, [f for t, f in hits if not t.startswith("compile-ahead")]


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, F.P, shape).astype(np.uint32))


def test_kernel_rounds_compiled_ahead(compiles):
    K, d, n = 1, 1, 8
    for futs in SR.rounds_ahead(K, d, n, interpret=True):
        for fut in futs:
            fut.result()
    c = _rand((K, 4, 1, 128), 1)
    for r in range(n.bit_length() - 1):
        tiles = (_rand((K, 4, 1, 128), r),)
        SR._eval_round(tiles, n >> r, True)
        SR._fold_round(tiles, c, n >> r, True)
    pool, caller = _split(compiles, "_eval_round", "_fold_round")
    assert len(pool) == 6 and caller == []


def test_reference_rounds_compiled_ahead(compiles, monkeypatch):
    """The reference prover compiles its rounds ahead on a TPU."""
    monkeypatch.setattr(KOPS, "on_tpu", lambda: True)
    factors = [F.f4_from_base(_rand((16,), s)) for s in range(2)]
    with KOPS.thread_path("ref"):
        proof, _ = SC.prove(factors, Transcript("t"))
    monkeypatch.undo()
    with KOPS.thread_path("ref"):
        twin, _ = SC.prove(factors, Transcript("t"))
    np.testing.assert_array_equal(proof.round_polys, twin.round_polys)
    pool, caller = _split(compiles, "_round_kernel", "_fold_kernel")
    assert len(pool) == 8 and caller == []


def test_merkle_levels_compiled_ahead(compiles, monkeypatch):
    """``prepare`` names the leaf hash and every level's compression
    exactly as ``commit`` runs them (here in interpret mode)."""
    started = []
    real_start = AH.start

    def start(fn, *args, **static):
        started.append((fn.__name__, [a.shape for a in args]))
        return real_start(fn, *args, **dict(static, interpret=True))

    monkeypatch.setattr(AH, "start", start)
    monkeypatch.setattr(KOPS, "on_tpu", lambda: True)
    futs = M.prepare((2, 8, 24))
    monkeypatch.undo()
    assert started == [("hash_rows", [(2, 8, 24)])] + [
        ("compress_pairs", [(2, k, 8)] * 2) for k in (4, 2, 1)]
    for fut in futs:
        fut.result()
    leaves = _rand((2, 8, 24))
    KOPS.poseidon2_hash(leaves)
    for k in (4, 2, 1):
        KOPS.poseidon2_compress(_rand((2, k, 8)), _rand((2, k, 8), 1))
    pool, caller = _split(compiles, "hash_rows", "compress_pairs")
    assert len(pool) == 4 and caller == []


def test_e_vec_buckets_compiled_ahead(compiles, monkeypatch):
    """Every suffix bucket of an opening's e-vector compiles ahead, and
    the padded buckets give the naive fold's values."""
    rng = np.random.default_rng(3)
    m = 6
    pts = []
    for s, idx in ((0, 0), (2, 1), (2, 3), (3, 5), (6, 9)):
        p = rng.integers(0, F.P, (m, 4)).astype(np.uint32)
        for i in range(s):
            bit = (idx >> (s - 1 - i)) & 1
            p[i] = (F.R_MOD_P if bit else 0, 0, 0, 0)
        pts.append(p)
    gamma = _rand((4,), 4)
    monkeypatch.setattr(KOPS, "on_tpu", lambda: True)
    e = PCS._build_e_vec(1 << m, pts, gamma)
    pool, caller = _split(compiles, "_bucket_e_impl")
    assert len(pool) == 4 and caller == []
    monkeypatch.undo()
    naive = jnp.zeros((1 << m, 4), jnp.uint32)
    w = F.f4one(())
    for p in pts:
        naive = F.f4add(naive, F.f4mul(w[None], PCS.eq_points(jnp.asarray(p))))
        w = F.f4mul(w, gamma)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(naive))
