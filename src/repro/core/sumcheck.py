"""Generic sum-check prover/verifier over Fp4.

Proves claims of the form  S = sum_{z in {0,1}^m} prod_t P_t(z)  where each
P_t is a multilinear polynomial given by its evaluation vector (2^m, 4).
Per-round degree equals the number of factors (<= 3 in this codebase:
[A_r, B_c] for matmuls, [eq, v, f+alpha] for LogUp zero-checks).

Variables are bound from the most-significant index bit downward; the final
point is reported MSB-first, i.e. point[0] corresponds to the most
significant index bit — the global convention of mle.py.

Lock order (ranked in repro.analysis.locks): the module-level
``_BATCHER_LOCK`` guarding the batcher registry is rank 60 — it may be
acquired while engine/scheduler locks (ranks <= 50) are held, and only
rank-70 leaf locks may be taken while holding it.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import field as F
from .mle import fsum
from .transcript import Transcript

from repro.kernels import ahead as AH
from repro.kernels import ops as KOPS

# Optional cross-claim round batchers (runtime/engine.py installs one when a
# thread fleet proves layers concurrently on the fused kernel path).  Worker
# threads register with a batcher; their sum-check claims are then coalesced
# into multi-claim kernel launches.  Threads that never registered fall
# through to the direct path.  Several engines may prove concurrently (the
# gateway's resident service), so the hook is a tuple of active batchers —
# replaced atomically under a lock, read lock-free — and a thread is routed
# to the one batcher it registered with.
_ROUND_BATCHERS: tuple = ()
_BATCHER_LOCK = None


def _batcher_lock():
    global _BATCHER_LOCK
    if _BATCHER_LOCK is None:
        import threading
        _BATCHER_LOCK = threading.Lock()
    return _BATCHER_LOCK


def add_round_batcher(batcher) -> None:
    global _ROUND_BATCHERS
    with _batcher_lock():
        _ROUND_BATCHERS = _ROUND_BATCHERS + (batcher,)


def remove_round_batcher(batcher) -> None:
    global _ROUND_BATCHERS
    with _batcher_lock():
        _ROUND_BATCHERS = tuple(b for b in _ROUND_BATCHERS
                                if b is not batcher)


def set_round_batcher(batcher) -> None:
    """Legacy single-batcher hook: replace the active set wholesale."""
    global _ROUND_BATCHERS
    with _batcher_lock():
        _ROUND_BATCHERS = () if batcher is None else (batcher,)


@jax.jit
def _round_kernel(factors: Tuple[jnp.ndarray, ...]):
    """One sum-check round: returns (g evals at X=0..d, los, diffs)."""
    d = len(factors)
    half = factors[0].shape[0] // 2
    los = tuple(f[:half] for f in factors)
    his = tuple(f[half:] for f in factors)
    diffs = tuple(F.f4sub(h, l) for h, l in zip(his, los))
    cur = list(los)
    evals = []
    for t in range(d + 1):
        if t > 0:
            cur = [F.f4add(c, dd) for c, dd in zip(cur, diffs)]
        prod = cur[0]
        for f in cur[1:]:
            prod = F.f4mul(prod, f)
        evals.append(fsum(prod, axis=0))
    return jnp.stack(evals), los, diffs


@jax.jit
def _fold_kernel(los: Tuple[jnp.ndarray, ...], diffs: Tuple[jnp.ndarray, ...],
                 c: jnp.ndarray):
    cb = jnp.broadcast_to(c, los[0].shape)
    return tuple(F.f4add(l, F.f4mul(cb, dd)) for l, dd in zip(los, diffs))


@dataclasses.dataclass
class SumcheckProof:
    """Wire-compressed sum-check transcript.

    round_polys stores only g_t(1..d); g_t(0) is implied by the running sum
    (g(0) = S - g(1)), so the verifier reconstructs it instead of checking
    it — one field element per round saved, identical soundness.
    """
    round_polys: np.ndarray   # (m, d, 4) uint32 — g_t evaluated at X=1..d
    final_evals: np.ndarray   # (num_factors, 4) uint32 — P_t(rho)


def _smul4(x: jnp.ndarray, t: int) -> jnp.ndarray:
    """Multiply Fp4 array by small non-negative integer t."""
    acc = None
    base = x
    while t:
        if t & 1:
            acc = base if acc is None else F.f4add(acc, base)
        base = F.f4add(base, base)
        t >>= 1
    return acc if acc is not None else jnp.zeros_like(x)


def _rounds_ahead(d: int, n: int) -> list:
    """Start compiling the round and fold programs of every round of a
    d-factor sum-check over n values (the reference path on a TPU, where
    each compile takes seconds); per round, their futures."""
    def fs(k):
        return tuple(jax.ShapeDtypeStruct((k, 4), jnp.uint32)
                     for _ in range(d))
    c = jax.ShapeDtypeStruct((4,), jnp.uint32)
    return [(AH.start(_round_kernel, fs(n >> r)),
             AH.start(_fold_kernel, fs(n >> (r + 1)), fs(n >> (r + 1)), c))
            for r in range(n.bit_length() - 1)]


def prove(factors: Sequence[jnp.ndarray], transcript: Transcript
          ) -> Tuple[SumcheckProof, jnp.ndarray]:
    """Run the sum-check prover. factors: list of (2^m, 4) Fp4 arrays.

    Returns (proof, point (m,4)). The claimed sum must already have been
    absorbed by the caller (it gates nothing here but keeps transcripts tied).
    """
    factors = [jnp.asarray(f) for f in factors]
    n = factors[0].shape[0]
    assert all(f.shape == (n, 4) for f in factors)
    m = n.bit_length() - 1
    assert 1 << m == n, "factor length must be a power of two"
    d = len(factors)

    if m and KOPS.use_fused():
        return _prove_fused(factors, transcript)

    challenges: List[jnp.ndarray] = []
    round_polys = []
    factors = tuple(factors)
    ahead = _rounds_ahead(d, n) if KOPS.on_tpu() else None
    for r in range(m):
        if ahead:
            for fut in ahead[r]:
                AH.wait(fut)
        g, los, diffs = _round_kernel(factors)
        round_polys.append(np.asarray(g)[1:])   # g(0) implied by running sum
        transcript.absorb(g)
        c = transcript.challenge_f4()
        challenges.append(c)
        factors = _fold_kernel(los, diffs, c)

    final_evals = jnp.stack([f[0] for f in factors])  # (d, 4)
    transcript.absorb(final_evals)
    # challenges[0] bound the most-significant index bit; under the global
    # convention (mle.py: point[0] <-> MSB) the point is just the challenge
    # sequence in order.
    point = jnp.stack(challenges) if m else jnp.zeros((0, 4), jnp.uint32)
    return SumcheckProof(round_polys=np.stack(round_polys) if m else
                         np.zeros((0, d, 4), np.uint32),
                         final_evals=np.asarray(final_evals)), point


def _prove_fused(factors: Sequence[jnp.ndarray], transcript: Transcript
                 ) -> Tuple[SumcheckProof, jnp.ndarray]:
    """Fused-kernel prover: all m rounds (g evals + absorb + challenge +
    fold) run as Pallas launches under one jit, transcripts byte-identical
    to the reference loop above (exact mod-p arithmetic is order-free and
    the kernel replicates the sponge schedule element-for-element)."""
    for batcher in _ROUND_BATCHERS:
        if batcher.registered():
            return batcher.prove(tuple(factors), transcript)
    rp, pts, finals, states = KOPS.sumcheck_prove_rounds(
        tuple(factors), transcript.state)
    transcript.set_state(states[0])
    rp_np, finals_np = jax.device_get((rp, finals))     # one host sync
    return SumcheckProof(round_polys=np.ascontiguousarray(rp_np[0, :, 1:]),
                         final_evals=finals_np[0]), pts[0]


@jax.jit
def _lagrange_eval(g: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Evaluate the degree-d poly given by evals g at X=0..d, at Fp4 point c."""
    dp1 = g.shape[0]
    # weights w_i = prod_{j != i} (i - j)  (small ints, exact)
    terms = []
    for i in range(dp1):
        w = 1
        for j in range(dp1):
            if j != i:
                w = (w * (i - j)) % F.P
        w_inv = F.fconst(pow(w, F.P - 2, F.P))
        num = None  # prod_{j != i} (c - j)
        for j in range(dp1):
            if j != i:
                cj = F.f4sub(c, F.f4_from_base(F.fconst(j)))
                num = cj if num is None else F.f4mul(num, cj)
        term = F.f4mul(num, F.f4_from_base(w_inv))
        terms.append(F.f4mul(term, g[i]))
    acc = terms[0]
    for t in terms[1:]:
        acc = F.f4add(acc, t)
    return acc


def verify(claimed_sum: jnp.ndarray, proof: SumcheckProof, num_factors: int,
           transcript: Transcript) -> Tuple[bool, jnp.ndarray, jnp.ndarray]:
    """Verify a sum-check proof.

    Returns (ok, point (m,4), final_evals (d,4)). The caller must separately
    validate each final factor evaluation (via PCS openings / direct evals).
    """
    if (not isinstance(proof.round_polys, np.ndarray)
            or proof.round_polys.ndim != 3
            or proof.round_polys.dtype != np.uint32):
        return False, None, None
    m = proof.round_polys.shape[0]
    d = num_factors
    running = jnp.asarray(claimed_sum)
    challenges = []
    for t in range(m):
        g_tail = jnp.asarray(proof.round_polys[t])
        if g_tail.shape != (d, 4):
            return False, None, None
        # g(0) is implied: g(0) = running - g(1). Reconstruct the full poly
        # so the transcript absorbs exactly what the prover absorbed.
        g0 = F.f4sub(running, g_tail[0])
        g = jnp.concatenate([g0[None, :], g_tail], axis=0)
        transcript.absorb(g)
        c = transcript.challenge_f4()
        challenges.append(c)
        running = _lagrange_eval(g, c)
    final_evals = jnp.asarray(proof.final_evals)
    transcript.absorb(final_evals)
    prod = final_evals[0]
    for i in range(1, final_evals.shape[0]):
        prod = F.f4mul(prod, final_evals[i])
    if not np.array_equal(np.asarray(prod), np.asarray(running)):
        return False, None, None
    point = jnp.stack(challenges) if m else jnp.zeros((0, 4), jnp.uint32)
    return True, point, final_evals
