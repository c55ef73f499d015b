"""Pallas kernels: batched Poseidon2 permutation and sponge over BabyBear.

Merkle commits hash thousands of leaves at once.  The kernels hold the
batch in a lane-major layout: a (16, rows, 128) array whose leading axis is
the state lane and whose trailing (rows, 128) tile carries one state per
VPU element.  Every round is then elementwise over full (8, 128) vregs;
the 4x4 external blocks and the internal sum are adds between the 16
leading slices, so nothing reshapes or scatters across the minor
dimension (the TPU compiler refuses both).  Round constants arrive as
an SMEM operand, since Pallas forbids captured device arrays.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import field as F
from repro.core import poseidon2 as P2

LANES = 128
ROW_BLOCK = 64          # sublane rows of 128 states per grid step

# Round constants as one flat Montgomery table: RF*WIDTH full-round
# constants, then RP partial-round constants.  Kernels take it as an SMEM
# operand and the rounds run under fori_loop, which keeps the kernel body
# one round long (a fully unrolled body compiles ~10x slower).
ROUND_CONSTANTS = np.concatenate(
    [(P2._RC_FULL * F._R % F.P).reshape(-1),
     P2._RC_PART * F._R % F.P]).astype(np.uint32)
_RCP0 = P2.RF * P2.WIDTH
_DIAG = [np.uint32(v) for v in (P2._DIAG * F._R % F.P).astype(np.uint32)]


def _external_linear(s):
    blocks = []
    for b in range(P2.WIDTH // 4):
        x = s[4 * b:4 * b + 4]
        out = []
        for i in range(4):
            acc = P2._smul(x[0], int(P2._M4[i, 0]))
            for j in range(1, 4):
                acc = F.fadd(acc, P2._smul(x[j], int(P2._M4[i, j])))
            out.append(acc)
        blocks.append(out)
    tot = blocks[0]
    for blk in blocks[1:]:
        tot = [F.fadd(a, b) for a, b in zip(tot, blk)]
    return [F.fadd(blk[i], tot[i]) for blk in blocks for i in range(4)]


def _internal_linear(s):
    tot = s[0]
    for x in s[1:]:
        tot = F.fadd(tot, x)
    return [F.fadd(F.fmul(x, d), tot) for x, d in zip(s, _DIAG)]


def permute_lanes(s, rc_ref):
    """Poseidon2 on a state given as a list of WIDTH same-shape arrays (one
    per state lane), round constants read from ``rc_ref`` (the
    ``ROUND_CONSTANTS`` table).  Bit-identical to ``P2.permute`` lane by
    lane; shared by the permutation, sponge and sum-check round kernels."""
    def full_round(r, s):
        return tuple(_external_linear(
            [P2._sbox(F.fadd(x, rc_ref[r * P2.WIDTH + i]))
             for i, x in enumerate(s)]))

    def partial_round(r, s):
        s0 = P2._sbox(F.fadd(s[0], rc_ref[_RCP0 + r]))
        return tuple(_internal_linear([s0] + list(s[1:])))

    s = tuple(_external_linear(list(s)))
    s = jax.lax.fori_loop(0, P2.RF // 2, full_round, s)
    s = jax.lax.fori_loop(0, P2.RP, partial_round, s)
    s = jax.lax.fori_loop(P2.RF // 2, P2.RF, full_round, s)
    return list(s)


def rc_spec() -> pl.BlockSpec:
    """BlockSpec placing the round-constant table in SMEM."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _row_tiling(n: int):
    """(rows, row_block) for n states: rows of 128 padded to a multiple of
    the block, the block a multiple of 8 sublanes or the whole array."""
    rows = -(-n // LANES)
    if rows <= 8:
        return rows, rows
    blk = min(ROW_BLOCK, -(-rows // 8) * 8)
    return -(-rows // blk) * blk, blk


def _to_lanes(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(k, n) lane-major values -> (k, rows, 128), zero padded."""
    k, n = x.shape
    return jnp.pad(x, ((0, 0), (0, rows * LANES - n))).reshape(k, rows,
                                                                LANES)


def _permute_kernel(rc_ref, x_ref, o_ref):
    out = permute_lanes([x_ref[i] for i in range(P2.WIDTH)], rc_ref)
    for i in range(P2.WIDTH):
        o_ref[i] = out[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def permute_batch(states: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """states: (n, 16) uint32 Montgomery -> permuted states."""
    n = states.shape[0]
    rows, blk = _row_tiling(n)
    spec = pl.BlockSpec((P2.WIDTH, blk, LANES), lambda i: (0, i, 0))
    out = pl.pallas_call(
        _permute_kernel,
        grid=(rows // blk,),
        in_specs=[rc_spec(), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((P2.WIDTH, rows, LANES), jnp.uint32),
        interpret=interpret,
    )(ROUND_CONSTANTS, _to_lanes(states.T, rows))
    return out.reshape(P2.WIDTH, -1)[:, :n].T


def _sponge_kernel(rc_ref, x_ref, o_ref, st_ref, *, n_elems: int):
    """Grid (row blocks, chunks): one RATE chunk absorbed per step, the
    sponge state resident in VMEM scratch across the chunk axis."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)
        st_ref[P2.RATE] = jnp.full(st_ref.shape[1:],
                                   np.uint32(n_elems * F._R % F.P), jnp.uint32)

    s = [st_ref[i] for i in range(P2.WIDTH)]
    s = [F.fadd(s[e], x_ref[0, e]) for e in range(P2.RATE)] + s[P2.RATE:]
    s = permute_lanes(s, rc_ref)
    for i in range(P2.WIDTH):
        st_ref[i] = s[i]

    @pl.when(j == pl.num_programs(1) - 1)
    def _digest():
        for e in range(P2.DIGEST):
            o_ref[e] = s[e]


def _sponge(flat: jnp.ndarray, n_elems: int, interpret: bool) -> jnp.ndarray:
    """flat: (rows, chunks * RATE) zero-padded messages -> (rows, DIGEST)."""
    n, width = flat.shape
    chunks = width // P2.RATE
    rows, blk = _row_tiling(n)
    x = _to_lanes(flat.T, rows).reshape(chunks, P2.RATE, rows, LANES)
    out = pl.pallas_call(
        functools.partial(_sponge_kernel, n_elems=n_elems),
        grid=(rows // blk, chunks),
        in_specs=[rc_spec(),
                  pl.BlockSpec((1, P2.RATE, blk, LANES),
                               lambda i, j: (j, 0, i, 0))],
        out_specs=pl.BlockSpec((P2.DIGEST, blk, LANES),
                               lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((P2.DIGEST, rows, LANES), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((P2.WIDTH, blk, LANES), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ROUND_CONSTANTS, x)
    return out.reshape(P2.DIGEST, -1)[:, :n].T


# ---------------------------------------------------------------------------
# Merkle-level hashing. Both entries reproduce the sponge/compression
# semantics of repro.core.poseidon2 exactly (same length tag, same chunk
# schedule, same Davies-Meyer feedforward) so commitments and Fiat-Shamir
# transcripts are byte-identical to the jnp reference path.
#
# On CPU (interpret=True, force_pallas=False) the reference jnp code runs
# directly under the jit — interpret-mode pallas_call costs seconds per
# distinct shape, which would dominate the CPU runs; force_pallas=True
# drives the real pallas_call wiring anyway (the differential tests do, on
# small shapes).
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("interpret", "force_pallas"))
def compress_pairs(left: jnp.ndarray, right: jnp.ndarray,
                   interpret: bool = True,
                   force_pallas: bool = False) -> jnp.ndarray:
    """2-to-1 compression of (..., DIGEST) node pairs, kernel-batched."""
    if interpret and not force_pallas:
        return P2.compress(left, right)
    batch = left.shape[:-1]
    states = jnp.concatenate([left, right], axis=-1).reshape(-1, P2.WIDTH)
    out = permute_batch(states, interpret)
    out = out[:, :P2.DIGEST].reshape(batch + (P2.DIGEST,))
    return F.fadd(out, left)


@functools.partial(jax.jit, static_argnames=("interpret", "force_pallas"))
def hash_rows(elems: jnp.ndarray, interpret: bool = True,
              force_pallas: bool = False) -> jnp.ndarray:
    """Sponge-hash along the trailing axis -> (..., DIGEST) digests.

    Matches ``poseidon2.hash_elems`` element-for-element: zero state with the
    unpadded length bound into the capacity lane, RATE-sized chunks added into
    the rate lanes, one permutation per chunk."""
    if interpret and not force_pallas:
        return P2.hash_elems(elems)
    batch = elems.shape[:-1]
    n = elems.shape[-1]
    pad = (-n) % P2.RATE
    if pad:
        elems = jnp.concatenate(
            [elems, jnp.zeros(batch + (pad,), dtype=jnp.uint32)], axis=-1)
    flat = elems.reshape(-1, elems.shape[-1])
    return _sponge(flat, n, interpret).reshape(batch + (P2.DIGEST,))
