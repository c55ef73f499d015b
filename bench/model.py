"""Weights and queries of a configuration, made from the run's seed.

Plain numpy, shared by the program under test and the reference: the
weights are the block's int16 fixed-point tensors (8 fractional bits),
stored transposed ``(d_out, d_in)`` and zero-padded to powers of two,
under the names the block's circuit declares.  Norms are those of the
repo's random-weight generator (std 0.6/sqrt(fan_in)), which keep every
activation inside the circuit's provable ranges.
"""
from __future__ import annotations

import math

import numpy as np

F8 = 8


def pad2(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


def rng(seed: int, *tags: int) -> np.random.Generator:
    """A generator for one stream of the run (weights of a layer, the
    queries of a client), fixed by the seed and the stream's tags."""
    return np.random.default_rng([int(seed) % (1 << 64), *tags])


def weight_shapes(block: dict) -> dict:
    d, qd = pad2(block["d"]), pad2(block["heads"] * block["dh"])
    ff = pad2(block["dff"])
    return {"wqT": (qd, d), "wkT": (qd, d), "wvT": (qd, d), "woT": (d, qd),
            "w1T": (ff, d), "w2T": (d, ff), "g1": (d,), "g2": (d,),
            "bq": (qd,), "bk": (qd,), "bv": (qd,), "bo": (d,),
            "b1f": (ff,), "b2f": (d,), "be1": (d,), "be2": (d,)}


def layer_weights(block: dict, g: np.random.Generator,
                  scale: float = 0.6) -> dict:
    """One GPT-2 block's weights, padded lanes zero."""
    d, dff, qd = block["d"], block["dff"], block["heads"] * block["dh"]
    real = {"wqT": (qd, d), "wkT": (qd, d), "wvT": (qd, d), "woT": (d, qd),
            "w1T": (dff, d), "w2T": (d, dff), "bq": (qd,), "bk": (qd,),
            "bv": (qd,), "bo": (d,), "b1f": (dff,), "b2f": (d,),
            "be1": (d,), "be2": (d,), "g1": (d,), "g2": (d,)}
    w = {}
    for name, shape in weight_shapes(block).items():
        if name.startswith("w"):
            fan_in = dff if name == "w2T" else d
            a = g.normal(0.0, scale / math.sqrt(fan_in), shape)
        elif name.startswith("g"):
            a = 1.0 + g.normal(0.0, 0.02, shape)
        else:
            a = g.normal(0.0, 0.02, shape)
        q = np.clip(np.round(a * (1 << F8)), -(1 << 15), (1 << 15) - 1)
        q = q.astype(np.int64)
        mask = np.zeros(shape, bool)
        mask[tuple(slice(0, n) for n in real[name])] = True
        q[~mask] = 0
        w[name] = q
    return w


def weights(config: dict, seed: int) -> list:
    block = config["block"]
    return [layer_weights(block, rng(seed, 1, layer), config["weight_scale"])
            for layer in range(config["layers"])]


def query(config: dict, seed: int, client: int, index: int) -> np.ndarray:
    """Query ``index`` of ``client``: a (d_pad, seq) int16 f8 activation
    matrix, entries N(0, query_std), padded rows zero."""
    block = config["block"]
    g = rng(seed, 2, client, index)
    q = np.zeros((pad2(block["d"]), block["seq"]), np.int64)
    x = g.normal(0.0, config["query_std"], (block["d"], block["seq"]))
    q[:block["d"]] = np.clip(np.round(x * (1 << F8)), -(1 << 15),
                             (1 << 15) - 1)
    return q
