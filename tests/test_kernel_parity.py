"""Kernel differential harness: every Pallas entry point vs its jnp oracle.

The fused prover path (``NANOZK_KERNEL_PATH=fused``) is only sound if each
kernel is *bit-identical* to the reference implementation — BabyBear/Fp4
arithmetic is exact mod p, so there is no tolerance: a single differing
limb means a diverged Fiat-Shamir transcript and an invalid attestation.

Property-based (hypothesis, degrading to skips when absent — see
hypothesis_compat) with deterministic rng-driven twins so every kernel is
exercised either way.  Element strategies mix uniform field elements with
the carry-saturating edges (0, 1, p-1, p-2, 2^31-1 mod p) that stress the
Montgomery reduction paths.  The kernel cases pass ``force_pallas=True``,
which drives the real ``pallas_call`` bodies in interpret mode on small
shapes: the CPU prover otherwise runs the reference math under jit (see
kernels/sumcheck_round.py), which would compare the oracle with itself.
"""
import contextlib
import os

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core import field as F
from repro.core import mle as MLE
from repro.core import ntt as NTT
from repro.core import poseidon2 as P2
from repro.core import sumcheck as SC
from repro.core import transcript as TRS
from repro.kernels import ntt_kernel as NK
from repro.kernels import ops, ref
from repro.kernels import poseidon2_kernel as PK
from repro.kernels import sumcheck_fold as SF
from repro.kernels import sumcheck_round as SR

try:
    from jax.experimental import pallas as _pl  # noqa: F401
    HAVE_PALLAS = True
except Exception:                               # pragma: no cover
    HAVE_PALLAS = False

needs_pallas = pytest.mark.skipif(not HAVE_PALLAS,
                                  reason="Pallas unavailable")

P = F.P
# Carry-saturating limbs: additive identities, p-1/p-2 (maximal Montgomery
# products), and the 2^31 wrap-around neighborhood.
EDGES = [0, 1, 2, P - 1, P - 2, (1 << 31) % P, ((1 << 31) - 1) % P]

felt = st.one_of(st.integers(min_value=0, max_value=P - 1),
                 st.sampled_from(EDGES))


@contextlib.contextmanager
def kernel_path(path):
    """Force NANOZK_KERNEL_PATH for the duration (tests must not depend on
    the ambient CI value — the fused tier-1 run sets it globally)."""
    old = os.environ.get("NANOZK_KERNEL_PATH")
    os.environ["NANOZK_KERNEL_PATH"] = path
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("NANOZK_KERNEL_PATH", None)
        else:
            os.environ["NANOZK_KERNEL_PATH"] = old


def _mont(vals, shape):
    return F.f_from_int(np.asarray(vals, np.int64).reshape(shape))


def _f4(vals, n):
    return _mont(vals, (n, 4))


def _eq(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Sum-check: fused round kernel (g evals + absorb + squeeze + fold) vs the
# reference prover loop of core/sumcheck.py.
# ---------------------------------------------------------------------------
def _reference_prove(factors, state):
    """Reference sum-check transcript data on the jnp path."""
    tr = TRS.Transcript("parity")
    tr.set_state(state)
    with kernel_path("ref"):
        proof, point = SC.prove(list(factors), tr)
    return proof, np.asarray(point), np.asarray(tr.state)


def _check_prove_rounds(factors, state, **kw):
    rp, pts, finals, states = SR.prove_rounds(factors, state, **kw)
    proof, point, st_ref = _reference_prove(factors, state)
    _eq(np.asarray(rp)[0, :, 1:], proof.round_polys)
    _eq(np.asarray(pts)[0], point)
    _eq(np.asarray(finals)[0], proof.final_evals)
    _eq(np.asarray(states)[0], st_ref)


@needs_pallas
@given(st.lists(felt, min_size=8 * 4 * 2, max_size=8 * 4 * 2),
       st.integers(min_value=1, max_value=3),
       st.lists(felt, min_size=16, max_size=16))
@settings(max_examples=15, deadline=None)
def test_fused_round_prover_matches_reference(vals, d, seed_state):
    """Full fused prover (all rounds: evals, absorb, challenge, fold) is
    transcript-identical to the reference loop, for 1..3 factors."""
    n = 8
    factors = tuple(_f4(vals[t * n * 4:(t + 1) * n * 4], n)
                    for t in range(d)) if d <= 2 else tuple(
        _f4(vals[:n * 4], n) for _ in range(d))
    state = _mont(seed_state, (16,))
    _check_prove_rounds(factors, state, force_pallas=True)


@needs_pallas
def test_fused_round_prover_edge_values(rng):
    """Deterministic twin: uniform + all-zero + all-(p-1) factors (n as
    above, so the kernels compiled there are reused)."""
    n = 8
    state = F.f_from_int(rng.integers(0, P, (16,)))
    for d in (1, 2, 3):
        factors = tuple(
            F.f4_from_base(F.f_from_int(rng.integers(0, P, n)))
            for _ in range(d))
        _check_prove_rounds(factors, state, force_pallas=True)
    zeros = np.zeros((n, 4), np.uint32)
    tops = np.asarray(_f4([P - 1] * n * 4, n))
    _check_prove_rounds((zeros, tops), state, force_pallas=True)


@pytest.mark.parametrize("force_pallas", [False, pytest.param(
    True, marks=needs_pallas)])
def test_fused_round_prover_batched_claims(rng, force_pallas):
    """K stacked claims reproduce K independent single-claim transcripts —
    the property the engine's SumcheckRoundBatcher relies on, on the
    kernels' per-claim grid and on the CPU's vmapped rounds."""
    n, d, K = 8, 3, 3
    factors = [F.f_from_int(rng.integers(0, P, (K, n, 4)))
               for _ in range(d)]
    states = F.f_from_int(rng.integers(0, P, (K, 16)))
    rp, pts, finals, sts = SR.prove_rounds(tuple(factors), states,
                                           force_pallas=force_pallas)
    for k in range(K):
        fk = tuple(f[k] for f in factors)
        proof, point, st_ref = _reference_prove(fk, states[k])
        _eq(np.asarray(rp)[k, :, 1:], proof.round_polys)
        _eq(np.asarray(pts)[k], point)
        _eq(np.asarray(finals)[k], proof.final_evals)
        _eq(np.asarray(sts)[k], st_ref)


@needs_pallas
def test_fused_round_prover_force_pallas(rng):
    """The real pallas_call wiring (interpret mode) matches the reference
    prover bit-for-bit over every round of a lane-split (n <= 128)
    sum-check."""
    n = 8
    factors = tuple(F.f_from_int(rng.integers(0, P, (n, 4)))
                    for _ in range(2))
    state = F.f_from_int(rng.integers(0, P, (16,)))
    _check_prove_rounds(factors, state, force_pallas=True)


@needs_pallas
def test_round_kernels_row_tiles_force_pallas(rng, monkeypatch):
    """One row-split round (n > 128) streamed over several grid tiles (one
    row per tile) through the real pallas_call wiring, for one claim and
    for three on the per-claim grid: g, transcript, challenge and folded
    factors equal the reference round, exactly."""
    monkeypatch.setattr(SR, "ROW_BLOCK", 1)
    n, d = 512, 3
    for K in (1, 3):
        factors = tuple(F.f_from_int(rng.integers(0, P, (K, n, 4)))
                        for _ in range(d))
        states = F.f_from_int(rng.integers(0, P, (K, 16)))
        g_r, folded_r, st_r, c_r = SR._ref_round(factors, states)
        tiles = tuple(SR.to_tiles(f) for f in factors)
        g = SR._eval_round(tiles, n, interpret=True)
        st, c = SR._transcript_round(
            g, jnp.broadcast_to(states[:, :, None, None], (K, 16, 1, 128)),
            interpret=True)
        folded = SR._fold_round(tiles, c, n, interpret=True)
        _eq(g[..., 0, 0], g_r)
        _eq(st[:, :, 0, 0], st_r)
        _eq(c[:, :, 0, 0], c_r)
        for t, f in zip(folded, folded_r):
            _eq(t, SR.to_tiles(f))


# ---------------------------------------------------------------------------
# Sum-check fold kernel (satellite: block-reduction wrapper).
# ---------------------------------------------------------------------------
@given(st.lists(felt, min_size=32 * 4, max_size=32 * 4),
       st.integers(min_value=1, max_value=3), felt)
@settings(max_examples=15, deadline=None)
def test_fold_round_property(vals, d, cval):
    n = 32
    factors = [_f4(vals, n) for _ in range(d)]
    c = _mont([cval, 0, 0, 0], (4,))
    g, folded = SF.fold_round(factors, c, block=8)
    g_r, folded_r = ref.fold_round_ref(factors, c)
    _eq(g, g_r)
    for a, b in zip(folded, folded_r):
        _eq(a, b)


def test_fold_round_block_reduction(rng):
    """The per-block partial-g reduction of the fold kernel's host wrapper
    must be invariant to the grid split: a multi-block launch (half=32,
    block=4 -> 8 grid steps) equals the single-block launch and the
    unfused reference, exactly."""
    n, d = 64, 3
    factors = [F.f4_from_base(F.f_from_int(rng.integers(0, P, n)))
               for _ in range(d)]
    c = F.f4_from_base(F.fconst(12345))
    g_multi, folded_multi = SF.fold_round(factors, c, block=4)
    g_single, folded_single = SF.fold_round(factors, c, block=32)
    g_ref, folded_ref = ref.fold_round_ref(factors, c)
    _eq(g_multi, g_ref)
    _eq(g_multi, g_single)
    for a, b, r in zip(folded_multi, folded_single, folded_ref):
        _eq(a, r)
        _eq(b, r)


# ---------------------------------------------------------------------------
# Poseidon2: permutation, Merkle compression, sponge hashing.
# ---------------------------------------------------------------------------
def _felts(rng, shape, inputs):
    """Montgomery field elements: uniform, or drawn from the edges."""
    if inputs == "edges":
        return _mont(rng.choice(EDGES, shape), shape)
    return F.f_from_int(rng.integers(0, P, shape))


@given(st.lists(felt, min_size=4 * 16, max_size=4 * 16))
@settings(max_examples=15, deadline=None)
def test_poseidon2_permute_property(vals):
    states = _mont(vals, (4, 16))
    _eq(ops.poseidon2_permute(states), P2.permute(states))


@needs_pallas
@pytest.mark.parametrize("inputs", ["uniform", "edges"])
def test_poseidon2_compress_pairs(rng, inputs):
    left = _felts(rng, (6, P2.DIGEST), inputs)
    right = _felts(rng, (6, P2.DIGEST), inputs)
    got = PK.compress_pairs(left, right, force_pallas=True)
    _eq(got, P2.compress(left, right))


@needs_pallas
@pytest.mark.parametrize("n_elems", [1, 7, 8, 9, 24])
@pytest.mark.parametrize("inputs", ["uniform", "edges"])
def test_poseidon2_hash_rows(rng, n_elems, inputs):
    """Sponge schedule (length tag, RATE chunking, padding) matches
    hash_elems for lengths below/at/above one RATE chunk."""
    elems = _felts(rng, (5, n_elems), inputs)
    got = PK.hash_rows(elems, force_pallas=True)
    _eq(got, P2.hash_elems(elems))


@needs_pallas
def test_poseidon2_hash_edge_values():
    for v in (0, P - 1):                 # a shape hash_rows compiles
        elems = np.full((5, 9), v, np.uint32)
        _eq(PK.hash_rows(elems, force_pallas=True), P2.hash_elems(elems))


# ---------------------------------------------------------------------------
# NTT (Reed-Solomon encoding path).
# ---------------------------------------------------------------------------
@needs_pallas
@given(st.lists(felt, min_size=2 * 32, max_size=2 * 32),
       st.booleans())
@settings(max_examples=15, deadline=None)
def test_ntt_rows_property(vals, inverse):
    x = _mont(vals, (2, 32))
    _eq(NK.ntt_rows(x, inverse=inverse, force_pallas=True),
        NTT.ntt(x, inverse=inverse))


@needs_pallas
def test_ntt_rows_force_pallas(rng):
    x = F.f_from_int(rng.integers(0, P, (4, 16)))
    for inverse in (False, True):
        _eq(NK.ntt_rows(x, inverse=inverse, force_pallas=True),
            NTT.ntt(x, inverse=inverse))
    # edge rows: all-zero and all-(p-1)
    edges = np.stack([np.zeros(16, np.uint32),
                      np.asarray(_mont([P - 1] * 16, (16,)))])
    _eq(NK.ntt_rows(edges, force_pallas=True), NTT.ntt(edges))


# ---------------------------------------------------------------------------
# modmatmul + the partial-evaluation wrappers the fused prover routes
# through it (matmul_proof.prove, pcs openings).
# ---------------------------------------------------------------------------
@given(st.lists(felt, min_size=8 * 8, max_size=8 * 8),
       st.lists(felt, min_size=8 * 8, max_size=8 * 8))
@settings(max_examples=15, deadline=None)
def test_modmatmul_property(avals, bvals):
    a = _mont(avals, (8, 8))
    b = _mont(bvals, (8, 8))
    _eq(ops.modmatmul(a, b, bm=8, bn=8, bk=8), ref.modmatmul_ref(a, b))


def test_modmatmul_edge_values():
    tops = np.asarray(_mont([P - 1] * 64, (8, 8)))
    zeros = np.zeros((8, 8), np.uint32)
    _eq(ops.modmatmul(tops, tops, bm=8, bn=8, bk=8),
        ref.modmatmul_ref(tops, tops))
    _eq(ops.modmatmul(tops, zeros, bm=8, bn=8, bk=8),
        ref.modmatmul_ref(tops, zeros))


def test_partial_eval_mm_matches_mle(rng):
    """Kernel-backed eq^T A / B eq == the jnp halving-tree reference —
    the substitution matmul_proof.prove makes on the fused path."""
    mat = F.f_from_int(rng.integers(0, P, (16, 8)))
    r_rows = F.f_from_int(rng.integers(0, P, (4, 4)))
    r_cols = F.f_from_int(rng.integers(0, P, (3, 4)))
    _eq(ops.partial_eval_rows_mm(mat, r_rows),
        MLE.partial_eval_rows(mat, r_rows))
    _eq(ops.partial_eval_cols_mm(mat, r_cols),
        MLE.partial_eval_cols(mat, r_cols))


# ---------------------------------------------------------------------------
# End-to-end dispatch: sumcheck.prove under both env values of the switch.
# ---------------------------------------------------------------------------
def test_sumcheck_prove_env_switch_byte_identical(rng):
    """core.sumcheck.prove produces identical proofs AND identical
    transcript states under NANOZK_KERNEL_PATH=ref and =fused."""
    factors = [F.f_from_int(rng.integers(0, P, (32, 4)))
               for _ in range(2)]
    outs = {}
    for path in ("ref", "fused"):
        tr = TRS.Transcript("switch")
        with kernel_path(path):
            proof, point = SC.prove(list(factors), tr)
        outs[path] = (proof.round_polys, proof.final_evals,
                      np.asarray(point), np.asarray(tr.state))
    for a, b in zip(outs["ref"], outs["fused"]):
        _eq(a, b)
