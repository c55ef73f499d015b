"""BabyBear prime field arithmetic in pure uint32 JAX (Montgomery form).

TPU adaptation note (see DESIGN.md §2): TPUs expose 32-bit integer lanes and no
native 64-bit multiply, so all field arithmetic here is built from 16-bit limb
decomposition of 32x32->64 products, in plain ``jnp.uint32``. The same
representation is used by the Pallas kernels (``repro.kernels``), so the jnp
path below doubles as their oracle.

Conventions
-----------
* ``P = 15 * 2**27 + 1`` (BabyBear). Elements are stored in **Montgomery form**
  with ``R = 2**32``: an array ``a`` of dtype uint32 represents the field value
  ``a * R^-1 mod P``.
* ``Fp`` arrays: any-shape uint32. ``Fp4`` arrays: trailing axis of size 4
  (coefficients of x^0..x^3 in Fp[x]/(x^4 - W4)), each coefficient Montgomery.
* All functions are jit-friendly and shape-polymorphic.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Constants (computed exactly with Python ints at import time).
# ---------------------------------------------------------------------------
P = 15 * 2**27 + 1  # 2013265921, "BabyBear"
assert P < 2**31
TWO_ADICITY = 27
_R = 2**32
R_MOD_P = _R % P
# -P^{-1} mod 2^32 (Montgomery constant)
NEG_P_INV = (-pow(P, -1, _R)) % _R

# Multiplicative generator of Fp* (verified below) and 2-adic root chain.
GENERATOR = 31
assert pow(GENERATOR, (P - 1) // 2, P) != 1
assert pow(GENERATOR, (P - 1) // 3, P) != 1
assert pow(GENERATOR, (P - 1) // 5, P) != 1

# Binomial extension Fp4 = Fp[x]/(x^4 - W4). Irreducible iff W4 is a
# non-square and p = 1 mod 4 (Lidl-Niederreiter Thm 3.75).
W4 = 11
assert P % 4 == 1
assert pow(W4, (P - 1) // 2, P) != 1, "W4 must be a quadratic non-residue"

_U32 = jnp.uint32
_MASK16 = np.uint32(0xFFFF)


def _c(x: int) -> np.uint32:
    return np.uint32(x)


# ---------------------------------------------------------------------------
# 32x32 -> 64 multiply via 16-bit limbs (returns hi, lo uint32 words).
# ---------------------------------------------------------------------------
def _mul32_64(a: jnp.ndarray, b: jnp.ndarray):
    a0 = a & _MASK16
    a1 = a >> 16
    b0 = b & _MASK16
    b1 = b >> 16
    ll = a0 * b0            # < 2^32, exact in uint32
    lh = a0 * b1            # < 2^32
    hl = a1 * b0            # < 2^32
    hh = a1 * b1            # < 2^32
    # carry-aware middle column
    mid = (ll >> 16) + (lh & _MASK16) + (hl & _MASK16)   # <= 3*(2^16-1)
    lo = (ll & _MASK16) | ((mid & _MASK16) << 16)
    hi = hh + (lh >> 16) + (hl >> 16) + (mid >> 16)
    return hi, lo


# The primitives are individually jitted: un-jitted call sites (gadget
# glue, verifier claim combination) would otherwise pay ~10-30 op
# dispatches per field op — jitting made the verifier ~5x faster
# (EXPERIMENTS.md §Perf, prover iteration 4). Inside other jits these
# inline at trace time, costing nothing.
@jax.jit
def fmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Montgomery product: returns a*b*R^-1 mod P (both operands Montgomery)."""
    hi, lo = _mul32_64(a, b)
    m = lo * _c(NEG_P_INV)                      # mod 2^32 wrap is intended
    mhi, _mlo = _mul32_64(m, _c(P))
    carry = (lo != 0).astype(_U32)              # lo + mlo is 0 or 2^32 exactly
    t = hi + mhi + carry                        # < 2P, no uint32 overflow
    return jnp.where(t >= _c(P), t - _c(P), t)


@jax.jit
def fadd(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    s = a + b                                    # < 2^32 since a,b < P < 2^31
    return jnp.where(s >= _c(P), s - _c(P), s)


@jax.jit
def fsub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(a >= b, a - b, (a + _c(P)) - b)


@jax.jit
def fneg(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(a == 0, a, _c(P) - a)


def fpow(a: jnp.ndarray, e: int) -> jnp.ndarray:
    """a**e for a Python int exponent (unrolled square-and-multiply)."""
    result = jnp.full(jnp.shape(a), _c(R_MOD_P), dtype=_U32)  # Montgomery one
    base = a
    while e > 0:
        if e & 1:
            result = fmul(result, base)
        base = fmul(base, base)
        e >>= 1
    return result


@jax.jit
def finv(a: jnp.ndarray) -> jnp.ndarray:
    """Fermat inverse a^(P-2); inverse of 0 is 0 (callers must range-guard)."""
    return fpow(a, P - 2)


# ---------------------------------------------------------------------------
# Montgomery encode/decode.
# ---------------------------------------------------------------------------
_R_INV_MOD_P = pow(_R, -1, P)


# Host-side conversions: exact in int64 (both factors < 2^31), and with no
# device program per array shape to compile.
def f_from_int(x) -> jnp.ndarray:
    """numpy/int array (any signed ints) -> Montgomery Fp array."""
    arr = np.asarray(x, dtype=np.int64) % P
    return jnp.asarray((arr * R_MOD_P % P).astype(np.uint32))


def f_to_int(a: jnp.ndarray) -> np.ndarray:
    """Montgomery Fp array -> numpy int64 array of canonical values."""
    return np.asarray(jax.device_get(a), dtype=np.int64) * _R_INV_MOD_P % P


def fone(shape=()) -> jnp.ndarray:
    return jnp.full(shape, _c(R_MOD_P), dtype=_U32)


def fzero(shape=()) -> jnp.ndarray:
    return jnp.zeros(shape, dtype=_U32)


@functools.lru_cache(maxsize=4096)
def _fconst_cached(v: int, shape: tuple) -> np.ndarray:
    # numpy, not jnp: safe to cache across jit traces (a jnp.full inside a
    # trace is a tracer and must never be memoized), and jax treats the
    # cached array as a constant either way.
    return np.full(shape, _c(v * _R % P), dtype=np.uint32)


def fconst(v: int, shape=()) -> jnp.ndarray:
    """Montgomery constant for Python int v (cached per shape: un-jitted
    jnp.full costs ~0.3 ms of dispatch and the prover asks for the same
    small constants thousands of times per layer)."""
    if isinstance(shape, int):
        shape = (shape,)
    return _fconst_cached(v % P, tuple(shape))


# ---------------------------------------------------------------------------
# Fp4 = Fp[x]/(x^4 - W4). Arrays have trailing axis 4.
# ---------------------------------------------------------------------------
_W4M = _c((W4 * _R) % P)  # W4 in Montgomery form


@jax.jit
def f4_from_base(a: jnp.ndarray) -> jnp.ndarray:
    """Embed Fp -> Fp4 (constant coefficient)."""
    z = jnp.zeros(jnp.shape(a) + (3,), dtype=_U32)
    return jnp.concatenate([a[..., None], z], axis=-1)


def f4add(a, b):
    return fadd(a, b)


def f4sub(a, b):
    return fsub(a, b)


def f4neg(a):
    return fneg(a)


@jax.jit
def f4mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a0, a1, a2, a3 = (a[..., i] for i in range(4))
    b0, b1, b2, b3 = (b[..., i] for i in range(4))
    w = jnp.asarray(_W4M)

    def m(x, y):
        return fmul(x, y)

    c0 = fadd(m(a0, b0), fmul(w, fadd(fadd(m(a1, b3), m(a2, b2)), m(a3, b1))))
    c1 = fadd(fadd(m(a0, b1), m(a1, b0)), fmul(w, fadd(m(a2, b3), m(a3, b2))))
    c2 = fadd(fadd(m(a0, b2), m(a1, b1)), fadd(m(a2, b0), fmul(w, m(a3, b3))))
    c3 = fadd(fadd(m(a0, b3), m(a1, b2)), fadd(m(a2, b1), m(a3, b0)))
    return jnp.stack([c0, c1, c2, c3], axis=-1)


def f4mul_base(a4: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Multiply Fp4 array by base-field array (broadcast over coeff axis)."""
    return fmul(a4, b[..., None])


def f4pow(a: jnp.ndarray, e: int) -> jnp.ndarray:
    result = f4one(jnp.shape(a)[:-1])
    base = a
    while e > 0:
        if e & 1:
            result = f4mul(result, base)
        base = f4mul(base, base)
        e >>= 1
    return result


_INV_CHUNK = 1 << 16


@jax.jit
def f4inv(a: jnp.ndarray) -> jnp.ndarray:
    """Inverse in Fp4 via the norm map: a^-1 = conj / N(a).

    N(a) = a * a^p * a^{p^2} * a^{p^3} lies in Fp. Frobenius on the binomial
    basis is coefficient-wise: (x^i)^{p^j} = W4^{i(p^j-1)/4} x^i.

    Larger arrays are inverted 2^16 elements at a time: in one piece, the
    exponentiation chain's temporaries took 11 GiB at 2^25 elements on a
    TPU v5e.
    """
    n = a.size // 4
    if n > _INV_CHUNK and n % _INV_CHUNK == 0:
        return jax.lax.map(_f4inv, a.reshape(-1, _INV_CHUNK, 4)
                           ).reshape(a.shape)
    return _f4inv(a)


def _f4inv(a: jnp.ndarray) -> jnp.ndarray:
    shape = jnp.shape(a)[:-1]
    # Frobenius twists: gamma_j[i] = W4^{i*(p^j-1)/4} (precomputed ints).
    conj = f4one(shape)
    for j in (1, 2, 3):
        tw = [pow(W4, (i * (P**j - 1) // 4) % (P - 1), P) for i in range(4)]
        twm = jnp.asarray(np.array([(t * _R) % P for t in tw], dtype=np.uint32))
        aj = fmul(a, jnp.broadcast_to(twm, jnp.shape(a)))
        conj = f4mul(conj, aj)
    n = f4mul(a, conj)  # norm: lies in Fp -> coefficient 0
    n0_inv = finv(n[..., 0])
    return f4mul_base(conj, n0_inv)


def f4one(shape=()) -> jnp.ndarray:
    out = jnp.zeros(tuple(shape) + (4,), dtype=_U32)
    return out.at[..., 0].set(_c(R_MOD_P))


def f4zero(shape=()) -> jnp.ndarray:
    return jnp.zeros(tuple(shape) + (4,), dtype=_U32)


def f4_to_int(a: jnp.ndarray) -> np.ndarray:
    return f_to_int(a)


def f4_from_int(x) -> jnp.ndarray:
    return f_from_int(x)


# ---------------------------------------------------------------------------
# Reference helpers for tests (exact Python-int semantics via numpy int64).
# ---------------------------------------------------------------------------
def np_mulmod(a, b):
    return (np.asarray(a, np.int64) * np.asarray(b, np.int64)) % P


def np_addmod(a, b):
    return (np.asarray(a, np.int64) + np.asarray(b, np.int64)) % P


# ---------------------------------------------------------------------------
# Static-analysis metadata, consumed by ``repro.analysis.ranges``.
# ---------------------------------------------------------------------------
# Multiplications by these literal uint32 constants wrap mod 2^32 BY DESIGN:
# Montgomery reduction computes m = lo * (-P^-1) mod 2^32 (see fmul). The
# interval analyzer treats a possible wrap in any OTHER multiply as a
# finding, so intended wraps must be registered here.
WRAP_OK_CONSTANTS = frozenset({NEG_P_INV})

# Declared input bounds per primitive: name -> dict(fn, args, out).
#   args: tuple of (kind, shape) pairs; kinds are
#     "fp"  — Montgomery field element, canonical range [0, P)
#     "u32" — arbitrary machine word, [0, 2^32)
#   out: "fp" (every output must provably stay < P) or None (unchecked).
# ranges.py traces each fn to a jaxpr with its arguments bounded as
# declared and proves no integer intermediate can exceed its dtype — this
# registry is what turns the ``# < 2P, no uint32 overflow`` comments above
# into machine-checked facts.
ANALYSIS_BOUNDS = {
    "fmul": dict(fn=lambda a, b: fmul(a, b),
                 args=(("fp", (8,)), ("fp", (8,))), out="fp"),
    "fadd": dict(fn=lambda a, b: fadd(a, b),
                 args=(("fp", (8,)), ("fp", (8,))), out="fp"),
    "fsub": dict(fn=lambda a, b: fsub(a, b),
                 args=(("fp", (8,)), ("fp", (8,))), out="fp"),
    "fneg": dict(fn=lambda a: fneg(a), args=(("fp", (8,)),), out="fp"),
    "finv": dict(fn=lambda a: finv(a), args=(("fp", (8,)),), out="fp"),
    "f4_from_base": dict(fn=lambda a: f4_from_base(a),
                         args=(("fp", (8,)),), out="fp"),
    "f4mul": dict(fn=lambda a, b: f4mul(a, b),
                  args=(("fp", (8, 4)), ("fp", (8, 4))), out="fp"),
    "f4inv": dict(fn=lambda a: f4inv(a), args=(("fp", (8, 4)),), out="fp"),
    # Tightness witness: even for FULL-range uint32 operands the limb
    # product's hi word peaks at exactly 2^32 - 1 — no headroom, no wrap.
    "_mul32_64": dict(fn=lambda a, b: _mul32_64(a, b),
                      args=(("u32", (8,)), ("u32", (8,))), out=None),
}
