"""Multilinear-extension utilities over Fp / Fp4.

GLOBAL CONVENTION (binding for sumcheck.py, pcs.py, matmul_proof.py,
lookup.py, circuit.py):
* A vector ``v`` of length 2^m defines the multilinear polynomial V.
  An evaluation point is an Fp4 array of shape (m, 4) with **point[0]
  corresponding to the MOST significant index bit** (big-endian).
* A row-major matrix (R, C) flattened to length R*C has point layout
  ``concat([row_point, col_point])`` — row bits are the high bits.
* ``eq_points(r)`` returns the 2^m vector eq(r, .) under this indexing.
* Sum-check binds variables MSB-first and reports its point MSB-first,
  so sum-check points compose with these helpers without reversal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import field as F


@functools.partial(jax.jit, static_argnames=("axis",))
def fsum(x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Mod-p sum along ``axis`` via halving tree (works on Fp or Fp4 arrays).

    For Fp4 arrays the coefficient axis must not be the reduced axis.
    """
    x = jnp.moveaxis(x, axis, 0)
    n = x.shape[0]
    if n == 0:
        return jnp.zeros(x.shape[1:], dtype=jnp.uint32)
    while n > 1:
        half = n // 2
        lo, hi = x[:half], x[half:2 * half]
        rem = x[2 * half:]
        x = F.fadd(lo, hi)
        if rem.shape[0]:
            x = jnp.concatenate([x, rem], axis=0)
        n = x.shape[0]
    return x[0]


_EQ_SPLIT = 12


@jax.jit
def eq_points(r: jnp.ndarray) -> jnp.ndarray:
    """eq(r, x) for all x in {0,1}^m -> (2^m, 4). r: (m, 4) Fp4.

    eq factors over the variables, and exact arithmetic makes the grouping
    of the products invisible.  Up to ``_EQ_SPLIT`` variables the table is
    one scan over r, bit j of x choosing r_j or 1 - r_j; above it, the
    outer product of the tables of r's leading and trailing halves.  Both
    keep the program short: the doubling chain this replaces compiled in
    time growing with m (~50 s at m = 20 for a TPU v5e)."""
    m = r.shape[0]
    if m > _EQ_SPLIT:
        hi, lo = eq_points(r[:m // 2]), eq_points(r[m // 2:])
        return F.f4mul(hi[:, None, :], lo[None, :, :]).reshape(-1, 4)
    x = jnp.arange(1 << m, dtype=jnp.uint32)[:, None]

    def bind(acc, jr):
        j, rj = jr
        bit = (x >> (m - 1 - j)) & 1
        return F.f4mul(acc, jnp.where(bit == 1, rj,
                                      F.f4sub(F.f4one(()), rj))), None

    out, _ = jax.lax.scan(bind, F.f4one((1 << m,)),
                          (jnp.arange(m, dtype=jnp.uint32), r))
    return out


@jax.jit
def mle_eval_base(v: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Evaluate MLE of base-field vector v (2^m,) at Fp4 point r (m,4) -> (4,)."""
    eq = eq_points(r)                       # (2^m, 4)
    prod = F.fmul(eq, v[:, None])           # Fp4 * base, coefficient-wise
    return fsum(prod, axis=0)


@jax.jit
def mle_eval_f4(v: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Evaluate MLE of Fp4 vector v (2^m, 4) at point r (m,4) -> (4,)."""
    eq = eq_points(r)
    prod = F.f4mul(eq, v)
    return fsum(prod, axis=0)


@jax.jit
def partial_eval_rows(mat: jnp.ndarray, r_rows: jnp.ndarray) -> jnp.ndarray:
    """Given base matrix (R, C), bind row variables to r_rows -> Fp4 (C, 4).

    Row index bits are the HIGH bits of the flattened (row*C + col) index,
    i.e. r_rows is the LEADING part of the full point (C a power of two).
    """
    eq = eq_points(r_rows)                  # (R, 4)
    prod = F.fmul(eq[:, None, :], mat[:, :, None])  # (R, C, 4)
    return fsum(prod, axis=0)


@jax.jit
def partial_eval_cols(mat: jnp.ndarray, r_cols: jnp.ndarray) -> jnp.ndarray:
    """Bind column variables of base matrix (R, C) -> Fp4 (R, 4)."""
    eq = eq_points(r_cols)                  # (C, 4)
    prod = F.fmul(eq[None, :, :], mat[:, :, None])  # (R, C, 4)
    return fsum(prod, axis=1)


def lift_to_f4(v: jnp.ndarray) -> jnp.ndarray:
    """Base vector (n,) -> Fp4 (n, 4) with zero high coefficients."""
    return F.f4_from_base(v)


@jax.jit
def eq_eval(r: jnp.ndarray, rho: jnp.ndarray) -> jnp.ndarray:
    """eq~(r, rho) = prod_j (r_j rho_j + (1-r_j)(1-rho_j)) over Fp4.

    Order-symmetric, so it is convention-independent as long as r and rho
    pair up the same variables.  The m factors are computed as one vector
    and multiplied in order under ``lax.scan``: an unrolled chain of m
    Fp4 products fuses into a program whose run time grows ~4.5x per
    variable on CPU (seconds at m=14, never finishing at a GPT-2-small weight
    commitment's m=25).
    """
    one = F.f4one(r.shape[:1])
    terms = F.f4add(F.f4mul(r, rho),
                    F.f4mul(F.f4sub(one, r), F.f4sub(one, rho)))
    acc, _ = jax.lax.scan(lambda acc, t: (F.f4mul(acc, t), None),
                          F.f4one(()), terms)
    return acc


def pad_pow2(v: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    n = v.shape[axis]
    target = 1 << max((n - 1).bit_length(), 0) if n > 1 else 1
    if target == n:
        return v
    pad_widths = [(0, 0)] * v.ndim
    pad_widths[axis] = (0, target - n)
    return jnp.pad(v, pad_widths)
