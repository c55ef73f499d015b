"""Jit'd wrappers selecting compiled Pallas kernels (TPU) or interpret mode.

On TPU the kernels run compiled; on CPU interpret=True executes the kernel
bodies for correctness validation — the mode the test suite sweeps shapes
in. ``on_tpu()`` picks per backend.

Kernel path switch
------------------
The prover has two implementations:

* ``fused`` — the Pallas kernel path: sum-check round kernels
  (``sumcheck_round.py``), kernel-batched Poseidon2 Merkle hashing,
  modmatmul-backed partial evaluations, and the NTT kernel for RS
  encoding.  The default on TPU, where every kernel runs compiled.
* ``ref`` — the pure-jnp reference path in ``repro.core``.  The default
  everywhere else, and the oracle the kernel path is checked against.

``NANOZK_KERNEL_PATH=ref|fused`` overrides the platform default, and
``thread_path`` overrides both for the calling thread (one process can
then prove on both paths at once, as ``chip_smoke.py`` does).

The switch is environment-driven and deliberately independent of
``VerifyPolicy``: it changes *how* proofs are computed, never *what* is
proved.  Both paths must produce byte-identical transcripts/attestations
(the ref path is the oracle — see ``tests/test_kernel_parity.py``); a
fused path that diverges by even one bit yields an invalid attestation.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax

from . import modmatmul as _mm
from . import ntt_kernel as _ntt
from . import poseidon2_kernel as _p2
from . import sumcheck_fold as _fold
from . import sumcheck_round as _round

KERNEL_PATHS = ("ref", "fused")
_THREAD = threading.local()


def kernel_path() -> str:
    """Active prover kernel path: 'ref' (jnp oracle) or 'fused' (Pallas);
    the calling thread's ``thread_path``, else NANOZK_KERNEL_PATH, else
    by platform."""
    p = getattr(_THREAD, "path", None)
    if p:
        return p
    p = os.environ.get("NANOZK_KERNEL_PATH", "").strip().lower()
    if p and p not in KERNEL_PATHS:
        raise ValueError(
            f"NANOZK_KERNEL_PATH={p!r}: expected one of {KERNEL_PATHS}")
    return p or ("fused" if on_tpu() else "ref")


@contextlib.contextmanager
def thread_path(path: str):
    """Prove on ``path`` in the calling thread only, for the block."""
    if path not in KERNEL_PATHS:
        raise ValueError(f"kernel path {path!r}: expected one of "
                         f"{KERNEL_PATHS}")
    old = getattr(_THREAD, "path", None)
    _THREAD.path = path
    try:
        yield
    finally:
        _THREAD.path = old


def use_fused() -> bool:
    return kernel_path() == "fused"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def modmatmul(a, b, **kw):
    kw.setdefault("interpret", not on_tpu())
    return _mm.modmatmul(a, b, **kw)


def poseidon2_permute(states, **kw):
    kw.setdefault("interpret", not on_tpu())
    return _p2.permute_batch(states, **kw)


def poseidon2_compress(left, right, **kw):
    kw.setdefault("interpret", not on_tpu())
    return _p2.compress_pairs(left, right, **kw)


def poseidon2_hash(elems, **kw):
    kw.setdefault("interpret", not on_tpu())
    return _p2.hash_rows(elems, **kw)


def ntt(x, inverse: bool = False, **kw):
    kw.setdefault("interpret", not on_tpu())
    return _ntt.ntt_rows(x, inverse=inverse, **kw)


def sumcheck_fold(factors, c, **kw):
    kw.setdefault("interpret", not on_tpu())
    return _fold.fold_round(factors, c, **kw)


def sumcheck_prove_rounds(factors, states, **kw):
    """Fused multi-claim sum-check prover (see sumcheck_round.prove_rounds)."""
    kw.setdefault("interpret", not on_tpu())
    return _round.prove_rounds(factors, states, **kw)


# ---------------------------------------------------------------------------
# Kernel-backed multilinear partial evaluations (fused-path replacements for
# mle.partial_eval_rows / partial_eval_cols).  eq^T @ mat and mat @ eq are
# exact mod-p matmuls, so the modmatmul kernel's chunked fadd-tree reduction
# produces identical field values to the jnp halving-tree reference.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("interpret",))
def _partial_rows_impl(mat, eq, interpret):
    return _mm.modmatmul(eq.T, mat, interpret=interpret).T


@functools.partial(jax.jit, static_argnames=("interpret",))
def _partial_cols_impl(mat, eq, interpret):
    # (eq^T mat^T)^T keeps the 4-wide Fp4 axis off the kernel's lane dim
    return _mm.modmatmul(eq.T, mat.T, interpret=interpret).T


def partial_eval_rows_mm(mat, r_rows, **kw):
    """(R, C) Fp matrix, bind row bits at r_rows ((log R, 4)) -> (C, 4)."""
    from repro.core.mle import eq_points
    kw.setdefault("interpret", not on_tpu())
    return _partial_rows_impl(mat, eq_points(r_rows), kw["interpret"])


def partial_eval_cols_mm(mat, r_cols, **kw):
    """(R, C) Fp matrix, bind col bits at r_cols ((log C, 4)) -> (R, 4)."""
    from repro.core.mle import eq_points
    kw.setdefault("interpret", not on_tpu())
    return _partial_cols_impl(mat, eq_points(r_cols), kw["interpret"])


# ---------------------------------------------------------------------------
# Static-analysis entry registry, consumed by ``repro.analysis.ranges``.
#
# Every public kernel entry point above must appear here with its declared
# input bounds; the analyzer traces each fn to a jaxpr (through the real
# pallas_call for kernels that always launch one, and through the
# interpret-path jnp bodies otherwise) and proves no uint32 intermediate
# can overflow. Arg kinds: "fp" = Montgomery element < P, "u32" = any
# word, "state" = sponge state (Fp lanes). Shapes are small on purpose —
# the arithmetic schedule (and hence the interval flow) is shape-uniform,
# while interpret-mode pallas tracing costs seconds per distinct shape.
# ---------------------------------------------------------------------------
def _ae(fn, *args, out="fp", pallas=False):
    return dict(fn=fn, args=args, out=out, pallas=pallas)


ANALYSIS_ENTRIES = {
    "modmatmul": _ae(lambda a, b: modmatmul(a, b),
                     ("fp", (8, 8)), ("fp", (8, 8)), pallas=True),
    "poseidon2_permute": _ae(lambda s: poseidon2_permute(s),
                             ("fp", (8, 16)), pallas=True),
    "poseidon2_compress": _ae(lambda l, r: poseidon2_compress(l, r),
                              ("fp", (8, 8)), ("fp", (8, 8))),
    "poseidon2_compress_pallas": _ae(
        lambda l, r: poseidon2_compress(l, r, force_pallas=True),
        ("fp", (8, 8)), ("fp", (8, 8)), pallas=True),
    "poseidon2_hash": _ae(lambda x: poseidon2_hash(x), ("fp", (8, 24))),
    "poseidon2_hash_pallas": _ae(
        lambda x: poseidon2_hash(x, force_pallas=True),
        ("fp", (8, 24)), pallas=True),
    "ntt": _ae(lambda x: ntt(x), ("fp", (8, 16))),
    "ntt_inverse": _ae(lambda x: ntt(x, inverse=True), ("fp", (8, 16))),
    "ntt_pallas": _ae(lambda x: ntt(x, force_pallas=True),
                      ("fp", (8, 16)), pallas=True),
    "sumcheck_fold": _ae(
        lambda f0, f1, c: sumcheck_fold((f0, f1), c),
        ("fp", (16, 4)), ("fp", (16, 4)), ("fp", (4,)), pallas=True),
    "sumcheck_prove_rounds": _ae(
        lambda f0, f1, st: sumcheck_prove_rounds((f0, f1), st),
        ("fp", (8, 4)), ("fp", (8, 4)), ("fp", (16,))),
    "sumcheck_prove_rounds_pallas": _ae(
        lambda f0, f1, st: sumcheck_prove_rounds((f0, f1), st,
                                                 force_pallas=True),
        ("fp", (512, 4)), ("fp", (512, 4)), ("fp", (16,)), pallas=True),
    "partial_eval_rows_mm": _ae(lambda m, r: partial_eval_rows_mm(m, r),
                                ("fp", (8, 8)), ("fp", (3, 4)), pallas=True),
    "partial_eval_cols_mm": _ae(lambda m, r: partial_eval_cols_mm(m, r),
                                ("fp", (8, 8)), ("fp", (3, 4)), pallas=True),
}
