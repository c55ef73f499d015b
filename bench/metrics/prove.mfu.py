"""The proved block's own forward operations per second (its QKV, output,
attention and MLP products, 2 per multiply-add, by ``work.forward_ops``),
times the window's layer proofs per second, over the int8 peak."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import work  # noqa: E402


def read(rec):
    layers = sum(q.get("layers", 0) for q in rec["queries"] if "wire" in q)
    if not layers or rec["window_s"] <= 0:
        return None
    ops_per_s = work.forward_ops(rec["config"]["block"]) * layers \
        / rec["window_s"]
    return 100.0 * ops_per_s / rec["peaks"]["int8_ops_per_s"]
