"""Pallas kernels: sum-check rounds, batched over claims.

Each round of the prover runs as three launches for a batch of K
independent claims sharing (n, d): an evaluation launch computes the round
polynomial g(0..d); a transcript launch absorbs g into each claim's
Fiat-Shamir sponge and squeezes the challenge; a fold launch binds the
variable at that challenge.  Evaluation and fold are gridded over (claim,
row tile), so factors of any length stream through VMEM, with running
partial sums of g in VMEM scratch.  The transcript launch depends only on
(K, d), and loops one permutation over its chunks.  Nothing syncs to the
host mid-prove.

Layout: a factor of n Fp4 values is held as (K, 4, R, 128) — coefficient
axis leading, R = max(n / 128, 1) rows of 128 lanes, lanes beyond n zero.
For n >= 256 the (lo, hi) halves are whole rows; for n <= 128 the high
half is brought to the low lanes by a lane rotation and the rest masked.
All reductions are halving adds over rows and rotate-and-add over lanes,
g and the transcript state ride as splat (1, 128) rows, and every value is
elementwise on vregs — nothing reshapes, gathers or scatters across the
minor dimension (the TPU compiler refuses those).

Byte-identity contract: BabyBear/Fp4 arithmetic is exact mod p, so any
evaluation/reduction order yields identical field values; the sponge
schedule here (length tag, RATE-chunk adds, one permutation per chunk, one
squeeze permutation per challenge) replicates ``core/transcript.py``
element-for-element.  Transcripts produced by these kernels are therefore
byte-identical to the reference path — enforced by
``tests/test_kernel_parity.py`` and the golden wire vectors.

The sponge state is a per-claim operand: claim k's transcript enters as
row k and leaves updated, so K claims from different layer proofs
(independent transcripts by construction) batch into the same launches —
the engine's ``SumcheckRoundBatcher`` exploits exactly this.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import field as F
from repro.core import poseidon2 as P2
from repro.core import sumcheck as SC
from repro.core import transcript as T
from . import ahead as AH
from . import poseidon2_kernel as PK

LANES = 128
ROW_BLOCK = 64          # rows of 128 per factor half per grid step

_W4 = np.uint32(F._W4M)


def _mont(v: int) -> np.uint32:
    """Montgomery-form scalar as a numpy literal (kernel-safe: no captured
    device constants)."""
    return np.uint32((v % F.P) * F._R % F.P)


# ---------------------------------------------------------------------------
# Kernel bodies.  Fp4 values are 4-tuples of same-shape Fp arrays.
# ---------------------------------------------------------------------------
def _f4add(a, b):
    return tuple(F.fadd(x, y) for x, y in zip(a, b))


def _f4sub(a, b):
    return tuple(F.fsub(x, y) for x, y in zip(a, b))


def _f4mul(a, b):
    """Same product as ``F.f4mul`` (x^4 = W4), on coefficient tuples."""
    m = F.fmul
    a0, a1, a2, a3 = a
    b0, b1, b2, b3 = b
    return (
        F.fadd(m(a0, b0), m(_W4, F.fadd(F.fadd(m(a1, b3), m(a2, b2)),
                                        m(a3, b1)))),
        F.fadd(F.fadd(m(a0, b1), m(a1, b0)),
               m(_W4, F.fadd(m(a2, b3), m(a3, b2)))),
        F.fadd(F.fadd(m(a0, b2), m(a1, b1)),
               F.fadd(m(a2, b0), m(_W4, m(a3, b3)))),
        F.fadd(F.fadd(m(a0, b3), m(a1, b2)), F.fadd(m(a2, b1), m(a3, b0))))


def _halves(f_refs, n: int):
    """(lo, hi) Fp4 tuples of every factor's current tile."""
    los, his = [], []
    for r in f_refs:
        x = r[0]
        if n > LANES:                    # (4, 2, rows, 128): whole rows
            los.append(tuple(x[c, 0] for c in range(4)))
            his.append(tuple(x[c, 1] for c in range(4)))
            continue
        half = n // 2                    # (4, 1, 128): split inside lanes
        keep = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) < half
        zero = jnp.zeros((1, LANES), jnp.uint32)
        los.append(tuple(jnp.where(keep, x[c], zero) for c in range(4)))
        his.append(tuple(
            jnp.where(keep, pltpu.roll(x[c], LANES - half, 1), zero)
            for c in range(4)))
    return los, his


def _sum_rows(x):
    """(rows, 128) -> (1, 128) exact mod-p halving sum over rows."""
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        x = F.fadd(x[:h], x[h:])
    return x


def _sum_lanes(x):
    """(1, 128) -> (1, 128) with the mod-p sum of all lanes in every lane."""
    s = LANES // 2
    while s:
        x = F.fadd(x, pltpu.roll(x, s, 1))
        s //= 2
    return x


def _eval_kernel(*refs, d: int, n: int):
    # refs: d factor tiles, then g (1, d+1, 4, 1, 128) and scratch acc
    f_refs = refs[:d]
    g_ref, acc_ref = refs[d:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    los, his = _halves(f_refs, n)
    diffs = [_f4sub(h, lo) for h, lo in zip(his, los)]
    cur = los
    for t in range(d + 1):
        if t:
            cur = [_f4add(x, dd) for x, dd in zip(cur, diffs)]
        prod = cur[0]
        for f in cur[1:]:
            prod = _f4mul(prod, f)
        for c in range(4):
            acc_ref[t, c] = F.fadd(acc_ref[t, c], _sum_rows(prod[c]))

    @pl.when(j == pl.num_programs(1) - 1)
    def _total():
        for t in range(d + 1):
            for c in range(4):
                g_ref[0, t, c] = _sum_lanes(acc_ref[t, c])


def _transcript_kernel(rc_ref, st_ref, g_ref, st_out, c_ref, *, d: int):
    """Absorb g (transcript._absorb_impl schedule) and squeeze one Fp4
    challenge (transcript.challenge_f4), on splat (1, 128) rows.  g_ref
    holds g zero-padded to whole RATE chunks (RATE / 4 Fp4 values each);
    each loop step adds one chunk, none in the squeeze step, and permutes,
    so the permutation is compiled once."""
    per = P2.RATE // 4
    chunks = g_ref.shape[1] // per
    s = [st_ref[0, i] for i in range(P2.WIDTH)]
    s[P2.RATE] = F.fadd(s[P2.RATE], _mont(4 * (d + 1)))

    def step(k, s):
        row = per * jnp.minimum(k, chunks - 1)
        live = k < chunks
        chunk = [jnp.where(live, g_ref[0, row + e // 4, e % 4], 0)
                 for e in range(P2.RATE)]
        s = [F.fadd(a, b) for a, b in zip(s, chunk)] + list(s[P2.RATE:])
        return tuple(PK.permute_lanes(s, rc_ref))

    s = jax.lax.fori_loop(0, chunks + 1, step, tuple(s))
    for i in range(P2.WIDTH):
        st_out[0, i] = s[i]
    for i in range(4):
        c_ref[0, i] = s[i]


def _fold_kernel(c_ref, *refs, d: int, n: int):
    # refs: d factor tiles, then d folded tiles (1, 4, rows, 128)
    c = tuple(c_ref[0, i] for i in range(4))         # splat (1, 128)
    los, his = _halves(refs[:d], n)
    for lo, hi, out in zip(los, his, refs[d:]):
        folded = _f4add(lo, _f4mul(c, _f4sub(hi, lo)))
        for i in range(4):
            out[0, i] = folded[i]


TILE_CHUNK = 1 << 16    # elements relaid out per step of to_tiles


@jax.jit
def to_tiles(f: jnp.ndarray) -> jnp.ndarray:
    """(K, n, 4) or (n, 4) factor -> the kernels' (K, 4, max(n/128, 1),
    128) layout.

    The relayout runs over chunks of ``TILE_CHUNK`` elements, each written
    in place into the output: the TPU compiler takes time linear in n for
    one whole-array relayout of an (n, 4) array (150 s at n = 2^25), and
    the chunk loop needs no temporary the size of the factor."""
    f = f.reshape(-1, f.shape[-2], 4)
    K, n, _ = f.shape
    if n < LANES:
        f = jnp.pad(f, ((0, 0), (0, LANES - n), (0, 0)))
        n = LANES
    c = min(n, TILE_CHUNK)

    def put(j, out):
        x = jax.lax.dynamic_slice(f, (0, j * c, 0), (K, c, 4))
        x = x.reshape(K, c // LANES, LANES, 4).transpose(0, 3, 1, 2)
        return jax.lax.dynamic_update_slice(
            out, x, (0, 0, j * (c // LANES), 0))

    return jax.lax.fori_loop(
        0, n // c, put, jnp.zeros((K, 4, n // LANES, LANES), jnp.uint32))


def _per_claim(*shape) -> pl.BlockSpec:
    return pl.BlockSpec((1,) + shape,
                        lambda k, *_: (k,) + (0,) * len(shape))


def _splats(K: int, *lead) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((K,) + lead + (1, LANES), jnp.uint32)


_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel",
                                                       "arbitrary"))


def _tiling(tiles, n: int):
    """Views, grid, factor spec and folded (rows, spec) for length n."""
    K = tiles[0].shape[0]
    if n <= LANES:
        spec = pl.BlockSpec((1, 4, 1, LANES), lambda k, j: (k, 0, 0, 0))
        return list(tiles), (K, 1), spec, 1, spec
    half_rows = n // (2 * LANES)
    blk = min(half_rows, ROW_BLOCK)
    views = [t.reshape(K, 4, 2, half_rows, LANES) for t in tiles]
    return (views, (K, half_rows // blk),
            pl.BlockSpec((1, 4, 2, blk, LANES), lambda k, j: (k, 0, 0, j, 0)),
            half_rows,
            pl.BlockSpec((1, 4, blk, LANES), lambda k, j: (k, 0, j, 0)))


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _eval_round(tiles, n: int, interpret: bool):
    """g(0..d) of every claim, (K, d+1, 4, 1, 128) splats.  tiles: d
    factors (K, 4, R, 128) of current length n; jitted per (K, n, d)."""
    d = len(tiles)
    views, grid, f_spec, _, _ = _tiling(tiles, n)
    return pl.pallas_call(
        functools.partial(_eval_kernel, d=d, n=n),
        grid=grid,
        in_specs=[f_spec] * d,
        out_specs=_per_claim(d + 1, 4, 1, LANES),
        out_shape=_splats(grid[0], d + 1, 4),
        scratch_shapes=[pltpu.VMEM((d + 1, 4, 1, LANES), jnp.uint32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(*views)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _transcript_round(g, states, interpret: bool):
    """Absorb g and squeeze the challenge: (states, c (K, 4, 1, 128)).
    Jitted per (K, d) only, so its permutation compiles once per degree
    and not once per factor length."""
    K, dp1 = g.shape[:2]
    per = P2.RATE // 4
    rows = -(-dp1 // per) * per
    g = jnp.pad(g, ((0, 0), (0, rows - dp1), (0, 0), (0, 0), (0, 0)))
    return pl.pallas_call(
        functools.partial(_transcript_kernel, d=dp1 - 1),
        grid=(K,),
        in_specs=[PK.rc_spec(), _per_claim(P2.WIDTH, 1, LANES),
                  _per_claim(rows, 4, 1, LANES)],
        out_specs=[_per_claim(P2.WIDTH, 1, LANES), _per_claim(4, 1, LANES)],
        out_shape=[_splats(K, P2.WIDTH), _splats(K, 4)],
        interpret=interpret,
    )(PK.ROUND_CONSTANTS, states, g)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _fold_round(tiles, c, n: int, interpret: bool):
    """Bind the leading variable of every factor at c: length n -> n/2."""
    d = len(tiles)
    views, grid, f_spec, out_rows, o_spec = _tiling(tiles, n)
    return tuple(pl.pallas_call(
        functools.partial(_fold_kernel, d=d, n=n),
        grid=grid,
        in_specs=[_per_claim(4, 1, LANES)] + [f_spec] * d,
        out_specs=[o_spec] * d,
        out_shape=[jax.ShapeDtypeStruct((grid[0], 4, out_rows, LANES),
                                        jnp.uint32) for _ in range(d)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
    )(c, *views))


@jax.jit
def _ref_round(factors, states):
    """The same round on the (K, n, 4) layout: the reference prover's round,
    transcript and fold code vmapped over claims.  This is the CPU
    execution of the kernel path; it stands in for the claim batching
    only, and the kernel bodies are tested through ``force_pallas``."""
    d = len(factors)
    g, los, diffs = jax.vmap(SC._round_kernel)(factors)   # g: (K, d+1, 4)
    states = jax.vmap(lambda s, e: T._absorb_any(s, e, 4 * (d + 1)))(
        states, g)
    states, c = jax.vmap(lambda s: T._squeeze_impl(s, 4))(states)
    return g, jax.vmap(SC._fold_kernel)(los, diffs, c), states, c


@jax.jit
def _absorb_finals(finals, states):
    """Absorb the final evals, exactly as the reference prover's
    epilogue does.  Jitted per (K, d), not per round count: the sponge
    permutation takes seconds to compile on a TPU."""
    d = finals.shape[1]
    return jax.vmap(lambda s, e: T._absorb_any(s, e, 4 * d))(states, finals)


def _epilogue(gs, cs, finals, states):
    """Stacked per-round outputs, the final evals and the new states."""
    return (jnp.stack(gs, axis=1), jnp.stack(cs, axis=1), finals,
            _absorb_finals(finals, states))


def rounds_ahead(K: int, d: int, n: int, interpret: bool) -> list:
    """Start compiling the evaluation and fold launches of every round
    of a (K, d) sum-check over n values; per round, their futures."""
    def tiles(nr):
        return tuple(jax.ShapeDtypeStruct((K, 4, max(nr // LANES, 1), LANES),
                                          jnp.uint32) for _ in range(d))
    c = jax.ShapeDtypeStruct((K, 4, 1, LANES), jnp.uint32)
    return [(AH.start(_eval_round, tiles(n >> r), n >> r, interpret),
             AH.start(_fold_round, tiles(n >> r), c, n >> r, interpret))
            for r in range(n.bit_length() - 1)]


def _prove_rounds_impl(factors, states, pallas: bool, interpret: bool):
    # A python loop of per-round jitted launches, NOT one enclosing jit:
    # the per-round units are cached by (K, n, d) and shared across all
    # sum-checks in a proof (an enclosing jit would recompile the whole
    # m-round graph per distinct n).  Nothing syncs to host mid-prove.
    n = factors[0].shape[-2]
    m = n.bit_length() - 1
    gs, cs = [], []
    if pallas:
        K = states.shape[0]
        # compiled kernels: every round's programs compile in parallel
        ahead = (None if interpret
                 else rounds_ahead(K, len(factors), n, interpret))
        # to_tiles takes (n, 4) factors as they come: an eager reshape
        # would copy each factor once more on the device
        tiles = tuple(to_tiles(f) for f in factors)
        st = jnp.broadcast_to(states[:, :, None, None],
                              (K, P2.WIDTH, 1, LANES))
        for r in range(m):
            if ahead:
                for fut in ahead[r]:
                    AH.wait(fut)
            g = _eval_round(tiles, n >> r, interpret)
            st, c = _transcript_round(g, st, interpret)
            tiles = _fold_round(tiles, c, n >> r, interpret)
            gs.append(g[..., 0, 0])
            cs.append(c[:, :, 0, 0])
        finals = jnp.stack([t[:, :, 0, 0] for t in tiles], axis=1)
        states = st[:, :, 0, 0]
    else:
        factors = tuple(f.reshape(-1, n, 4) for f in factors)
        for _ in range(m):
            g, factors, states, c = _ref_round(factors, states)
            gs.append(g)
            cs.append(c)
        finals = jnp.stack([f[:, 0] for f in factors], axis=1)
    return _epilogue(tuple(gs), tuple(cs), finals, states)


def prove_rounds(factors: Sequence[jnp.ndarray], states: jnp.ndarray,
                 interpret: bool = True, force_pallas: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run the full sum-check prover for K batched claims.

    factors: d arrays of shape (K, n, 4) — claim k's factor t is
    ``factors[t][k]``; n must be a power of two >= 2.  states: (K, 16)
    sponge states, one transcript per claim.  Single-claim callers may
    pass (n, 4) factors with a (16,) state and read row 0 of each output.

    On TPU (``interpret=False``) each round is three compiled Pallas
    launches.  On CPU the same round runs as plain jnp under one jit per
    round (interpret-mode pallas_call costs seconds per launch, which
    would dominate the CPU runs); ``force_pallas=True`` drives the real
    pallas_call in interpret mode anyway — used by the differential tests.

    Returns ``(round_polys (K, m, d+1, 4), points (K, m, 4),
    final_evals (K, d, 4), new_states (K, 16))`` — exactly the data the
    reference prover would have produced claim-by-claim, with transcripts
    advanced identically.
    """
    shape = jnp.shape(factors[0])
    n = shape[-2]
    assert all(jnp.shape(f) == shape for f in factors) and shape[-1] == 4
    assert n >= 2 and n & (n - 1) == 0
    factors = tuple(jnp.asarray(f) for f in factors)
    states = jnp.asarray(states).reshape(-1, P2.WIDTH)
    return _prove_rounds_impl(factors, states,
                              pallas=force_pallas or not interpret,
                              interpret=interpret)
