"""Plain reference of the attested block: the quantized GPT-2 block in numpy.

Written from the block's published semantics (16-bit fixed point with 8
fractional bits, round-half-up rescales, 2^16-entry lookup tables for
rsqrt, exp and GELU, a division-free softmax), independently of the
program under test: it imports nothing of it.  Activations are
feature-major ``(d_pad, seq)`` int64, rows past ``d`` zero.

``block_forward(block, w, x)`` is the reference.  ``bits`` < 16 is the
control: both operands of every projection (weights and activations)
rounded to that many signed bits with a per-tensor scale, as an int8
matrix unit would take them (int8 is the step below the stated int16),
and the rest computed as stated.  The control has to fail the
comparison.
"""
from __future__ import annotations

import functools
import math

import numpy as np

F8 = 8
LUT_SIZE = 1 << 16
# name -> (table domain's left end, input fractional bits, output bits)
LUTS = {"exp": (-4.0, 13, 6), "gelu": (-8.0, 12, 8), "rsqrt": (0.0, 12, 11)}
RSQRT_FLOOR = 0.01


def _gelu(x):
    erf = np.vectorize(math.erf, otypes=[np.float64])
    return 0.5 * x * (1.0 + erf(x / math.sqrt(2.0)))


@functools.lru_cache(maxsize=None)
def table(name: str) -> np.ndarray:
    """round(f(lo + i 2^-f_in) 2^f_out) for i in [0, 2^16)."""
    lo, f_in, f_out = LUTS[name]
    x = lo + np.arange(LUT_SIZE, dtype=np.float64) * 2.0 ** -f_in
    if name == "exp":
        y = np.exp(x)
    elif name == "gelu":
        y = _gelu(x)
    else:
        y = 1.0 / np.sqrt(np.maximum(x, RSQRT_FLOOR))
    return np.round(y * (1 << f_out)).astype(np.int64)


def _lookup(name: str, code: np.ndarray) -> np.ndarray:
    lo, f_in, _ = LUTS[name]
    i = code - int(round(lo * (1 << f_in)))
    if i.min() < 0 or i.max() >= LUT_SIZE:
        raise ValueError(f"{name} table input out of its domain")
    return table(name)[i]


def _shift(x: np.ndarray, s: int) -> np.ndarray:
    return (x + (1 << (s - 1))) >> s


def _check16(x: np.ndarray, what: str) -> np.ndarray:
    if x.min() < -(1 << 15) or x.max() >= (1 << 15):
        raise ValueError(f"{what} leaves the 16-bit range")
    return x


def _layernorm(x, g, b, d):
    """Mean over the d real rows; mean square into the rsqrt table at
    f=12; normalise at f=11 back to f=8; scale and shift."""
    s1 = x.sum(axis=0)
    mu = (s1 + d // 2) // d
    xc = x - mu[None, :]
    xc[d:, :] = 0
    D = d << 4
    ms = ((xc * xc).sum(axis=0) + D // 2) // D
    rst = _lookup("rsqrt", ms)
    xn = _check16(_shift(xc * rst[None, :], 11), "ln xn")
    return _check16(_shift(xn * g[:, None] + (b[:, None] << F8), F8), "ln y")


def _narrow(a: np.ndarray, bits: int) -> np.ndarray:
    """``a`` rounded to ``bits`` signed bits with a per-tensor scale, back
    on the integer grid; the identity at 16 bits."""
    if bits >= 16:
        return a
    scale = max(float(np.abs(a).max()), 1.0) / ((1 << (bits - 1)) - 1)
    return np.round(np.round(a / scale) * scale).astype(np.int64)


def _matmul(wT, x, bits):
    return _narrow(wT, bits) @ _narrow(x, bits)


def _linear(wT, x, b, bits):
    acc = _matmul(wT, x, bits) + (b[:, None] << F8)
    return _check16(_shift(acc, F8), "linear out")


def _head(q, k, v, dh):
    """One causal head: scores scaled by round(2^9/sqrt(dh)) into the exp
    table at f=13, softmax weights P = round(2^8 e / S) with ties broken
    down, output v P^T rescaled to f=8."""
    seq = q.shape[1]
    m = int(round((1 << 9) / math.sqrt(dh)))
    sidx = np.clip(_shift((q.T @ k) * m, 12), -(1 << 15), (1 << 15) - 1)
    e = _lookup("exp", sidx) * np.tril(np.ones((seq, seq), np.int64))
    S = e.sum(axis=1)[:, None]
    num = e << 8
    P = (num + S // 2) // S
    P -= (2 * (num - P * S) <= -S).astype(np.int64)
    return _check16(_shift(v @ P.T, F8), "attention out")


def block_forward(block: dict, w: dict, x: np.ndarray,
                  bits: int = 16) -> np.ndarray:
    """One GPT-2 block; ``block`` holds d, dff, heads, dh, seq."""
    d, H, dh = block["d"], block["heads"], block["dh"]
    x = x.astype(np.int64)
    y1 = _layernorm(x, w["g1"], w["be1"], d)
    q = _linear(w["wqT"], y1, w["bq"], bits)
    k = _linear(w["wkT"], y1, w["bk"], bits)
    v = _linear(w["wvT"], y1, w["bv"], bits)
    O = np.zeros_like(q)
    for h in range(H):
        sl = slice(h * dh, (h + 1) * dh)
        O[sl] = _head(q[sl], k[sl], v[sl], dh)
    hmid = _check16(x + _linear(w["woT"], O, w["bo"], bits), "residual")
    y2 = _layernorm(hmid, w["g2"], w["be2"], d)
    lo, f_in, _ = LUTS["gelu"]
    gidx = _shift(_matmul(w["w1T"], y2, bits) + (w["b1f"][:, None] << F8),
                  16 - f_in)
    a = _lookup("gelu", _check16(gidx, "gelu input"))
    f2 = _linear(w["w2T"], a, w["b2f"], bits)
    return _check16(hmid + f2, "block out")


def forward(block: dict, weights: list, x: np.ndarray,
            bits: int = 16) -> list:
    """Boundary activations h_0 .. h_L of a stack of blocks."""
    acts = [np.asarray(x, np.int64)]
    for w in weights:
        acts.append(block_forward(block, w, acts[-1], bits))
    return acts
