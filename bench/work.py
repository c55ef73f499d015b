"""Operations and bytes of the attested work, counted from shapes.

``forward_ops`` is the proved block's own forward: 2 operations per
multiply-add of the QKV, output, attention and MLP products at ``seq``,
at the published widths.  It counts the same work whatever implements
the prover, so a share of a peak made from it bounds every kernel's.

``hlo_bytes`` is the least traffic of one custom call: each operand read
once and each result written once, from the shapes in its HLO text.
"""
from __future__ import annotations

import re

import numpy as np

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
             "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_CALL = re.compile(r" ([a-z][\w.\-]*)\(")


def forward_ops(block: dict) -> int:
    """Operations of one layer's forward: QKV, output, attention, MLP."""
    d, dff, seq = block["d"], block["dff"], block["seq"]
    qd = block["heads"] * block["dh"]
    macs = (3 * d * qd * seq                               # q, k, v
            + qd * d * seq                                 # output
            + 2 * block["heads"] * seq * seq * block["dh"]  # qk^T, P v
            + 2 * d * dff * seq)                           # MLP up, down
    return 2 * macs


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in ``text`` (``u32[8,128]``)."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = int(np.prod([int(x) for x in dims.split(",") if x])) if dims \
            else 1
        total += n * _ITEMSIZE[dtype]
    return total


def hlo_bytes(hlo: str) -> int:
    """Least bytes one HLO instruction moves: its result shapes plus its
    operand shapes, from ``%x = <results> op(<operands>), ...``."""
    _, _, rhs = hlo.partition(" = ")
    m = _CALL.search(" " + rhs)
    if m is None:
        return 0
    results, depth = rhs[:m.start()], 1
    for end in range(m.end() - 1, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[end], 0)
        if depth == 0:
            break
    return shape_bytes(results) + shape_bytes(rhs[m.end() - 1:end])
