"""Pallas kernel: batched radix-2 NTT (Reed-Solomon row encoding).

One grid step transforms a VMEM-resident block of 8 rows end-to-end: all
log2(n) butterfly stages run against VMEM, so each row makes one HBM round
trip (the jnp reference path writes every stage back through HBM).

Layout: a row of length n >= 128 is held as (n/128, 128) and the block as
(n/128, 8, 128) — element e of row p sits at [e // 128, p, e % 128].
Shorter rows are packed 128/n to a 128-lane line.  A stage of half-width
h < 128 pairs lanes l and l ^ h: both partners come from lane rotations
(``pltpu.roll``) and a lane mask picks the butterfly side, so no stage
reshapes or gathers across the minor dimension (the TPU compiler refuses
both).  A stage with h >= 128 pairs whole (8, 128) tiles along the leading
axis.  Each stage loops over tiles, so compile time does not grow with n.
Twiddles arrive as one operand of per-stage (8, 128) tiles.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import field as F
from repro.core import ntt as NTT

LANES = 128
SUB = 8                 # rows of a transform per grid step (one sublane tile)


@functools.lru_cache(maxsize=None)
def stage_twiddles(n: int, inverse: bool) -> np.ndarray:
    """(tiles, 8, 128) Montgomery twiddles, stage by stage.

    A lane stage (h < 128) takes one tile, lane l holding w^((l mod h) *
    n/(2h)); a tile stage (h >= 128) takes h/128 tiles, tile j lane l
    holding w^((128 j + l) * n/(2h)) — the factor of the butterfly's high
    element at that position."""
    tw = NTT._twiddles(n, inverse)
    lane = np.arange(LANES)
    tiles = []
    for s in range(n.bit_length() - 1):
        h = 1 << s
        stride = n // (2 * h)
        if h < LANES:
            tiles.append(tw[(lane % h) * stride])
        else:
            for j in range(h // LANES):
                tiles.append(tw[(j * LANES + lane) * stride])
    return np.repeat(np.stack(tiles)[:, None, :], SUB, axis=1)


def _kernel(x_ref, tw_ref, o_ref, *, n: int, inverse: bool):
    # o_ref holds the (S, 8, 128) block, bit-reversed input, through every
    # stage; each stage is a fori_loop over (8, 128) tiles, so the kernel
    # body (and its compile time) does not grow with the row length
    S = x_ref.shape[0]
    o_ref[...] = x_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 1)
    t = 0
    for s in range(n.bit_length() - 1):
        h = 1 << s
        if h < LANES:
            def lane_stage(i, tw, h=h):
                x = o_ref[i]
                upper = (lane & h) != 0      # lane holds the butterfly's x[l+h]
                below = pltpu.roll(x, h, 1)          # x[l - h]
                above = pltpu.roll(x, LANES - h, 1)  # x[l + h]
                lo = jnp.where(upper, below, x)
                thi = F.fmul(jnp.where(upper, x, above), tw)
                o_ref[i] = jnp.where(upper, F.fsub(lo, thi), F.fadd(lo, thi))
                return tw
            jax.lax.fori_loop(0, S, lane_stage, tw_ref[t])
            t += 1
        else:
            hs = h // LANES

            def tile_stage(j, _, hs=hs, t=t):
                # pair j: tiles lo and lo + hs of butterfly group j // hs
                k = j & (hs - 1)
                lo_i = (j - k) * 2 + k
                lo, hi = o_ref[lo_i], o_ref[lo_i + hs]
                thi = F.fmul(hi, tw_ref[t + k])
                o_ref[lo_i] = F.fadd(lo, thi)
                o_ref[lo_i + hs] = F.fsub(lo, thi)
                return _
            jax.lax.fori_loop(0, S // 2, tile_stage, 0)
            t += hs
    if inverse:
        n_inv = np.uint32(pow(n, F.P - 2, F.P) * F._R % F.P)

        def scale(i, _):
            o_ref[i] = F.fmul(o_ref[i], n_inv)
            return _
        jax.lax.fori_loop(0, S, scale, 0)


def _pallas_ntt(x: jnp.ndarray, inverse: bool, interpret: bool
                ) -> jnp.ndarray:
    rows, n = x.shape
    x = x[:, NTT._bitrev(n)]
    per_line = max(LANES // n, 1)        # short rows packed per 128 lanes
    lines = -(-rows // per_line)
    lines_p = -(-lines // SUB) * SUB
    x = jnp.pad(x, ((0, lines_p * per_line - rows), (0, 0)))
    S = max(n // LANES, 1)
    x = x.reshape(lines_p, S, LANES).transpose(1, 0, 2)     # (S, lines, 128)
    tw = jnp.asarray(stage_twiddles(n, inverse))
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, inverse=inverse),
        grid=(lines_p // SUB,),
        in_specs=[pl.BlockSpec((S, SUB, LANES), lambda i: (0, i, 0)),
                  pl.BlockSpec(tw.shape, lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((S, SUB, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, lines_p, LANES), jnp.uint32),
        interpret=interpret,
    )(x, tw)
    return out.transpose(1, 0, 2).reshape(lines_p * per_line, n)[:rows]


@functools.partial(jax.jit,
                   static_argnames=("inverse", "interpret", "force_pallas"))
def ntt_rows(x: jnp.ndarray, inverse: bool = False, interpret: bool = True,
             force_pallas: bool = False) -> jnp.ndarray:
    """x: (rows, n) uint32 Montgomery; NTT along the trailing axis.

    The bit-reversal permutation happens outside the kernel (a gather XLA
    fuses into the feed); the kernel runs the log2(n) butterfly stages in
    one VMEM residency.

    On CPU (``interpret=True``) the identical butterfly schedule runs
    directly under the reference jit (``ntt._ntt_impl``) — interpret-mode
    pallas_call costs seconds per shape; ``force_pallas=True`` drives the
    real pallas_call wiring anyway (used by the differential tests on small
    shapes).
    """
    n = x.shape[-1]
    assert n & (n - 1) == 0
    if n == 1:
        return x
    if interpret and not force_pallas:
        return NTT._ntt_impl(x, inverse)
    return _pallas_ntt(x, inverse, interpret)
