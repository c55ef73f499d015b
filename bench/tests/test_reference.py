"""The plain reference against the program's quantized block, and its
control (int8 operands) against the reference, on the CPU."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.join(ROOT, "src"))
import check  # noqa: E402
import control  # noqa: E402
import model  # noqa: E402
import reference  # noqa: E402

BLOCKS = {"d16": {"d": 16, "dff": 32, "heads": 2, "dh": 8, "seq": 8},
          "d128": {"d": 128, "dff": 512, "heads": 4, "dh": 32, "seq": 8},
          "d768": {"d": 768, "dff": 3072, "heads": 12, "dh": 64, "seq": 8}}
SEEDS = [0, 1, 2, 2**31 + 11]


def _config(name, layers=2):
    return {"block": BLOCKS[name], "layers": layers, "weight_scale": 0.6,
            "query_std": 0.5}


@pytest.mark.parametrize("name", ["d16", "d128", "d768"])
@pytest.mark.parametrize("seed", SEEDS)
def test_reference_is_the_programs_block(name, seed):
    from repro.core import blocks as B
    block = BLOCKS[name]
    config = _config(name, layers=1 if name == "d768" else 2)
    cfg = B.BlockCfg(family="gpt2", d=block["d"], dff=block["dff"],
                     heads=block["heads"], kv_heads=block["heads"],
                     dh=block["dh"], seq=block["seq"])
    weights = model.weights(config, seed)
    x = model.query(config, seed, 0, 3)
    h = x
    for w, ref in zip(weights, reference.forward(block, weights, x)[1:]):
        h, _ = B.block_forward(cfg, w, h)
        assert np.array_equal(h, ref)


def test_tables_are_the_programs():
    from repro.core import luts
    for name in reference.LUTS:
        assert np.array_equal(reference.table(name),
                              luts.table_q(name).astype(np.int64))


@pytest.mark.parametrize("name", ["d16", "d128"])
@pytest.mark.parametrize("seed", SEEDS[:3])
def test_control_fails_the_comparison(name, seed):
    """int8 operands in the reference's place differ from the reference
    at many entries of every layer's output."""
    config = _config(name)
    weights = model.weights(config, seed)
    x = model.query(config, seed, 0, 0)
    ref = reference.forward(BLOCKS[name], weights, x)
    ctl = reference.forward(BLOCKS[name], weights, x, bits=8)
    assert np.array_equal(ref[0], ctl[0])
    assert all(np.count_nonzero(a != b) > 0.1 * BLOCKS[name]["d"]
               for a, b in zip(ref[1:], ctl[1:]))


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    return [(w["name"], files[w["config"]], w["traffic"])
            for w in bench["workloads"]]


@pytest.mark.parametrize("cell,config_file,traffic", _cells(),
                         ids=[c[0] for c in _cells()])
def test_control_fails_the_harness_count_at_the_cells_shapes(
        cell, config_file, traffic):
    """The control, at the cell's configuration and clients, read by the
    count that decides ``correct`` (``check.forward_mismatches``): above
    the limit, where the reference against itself reads 0."""
    with open(os.path.join(ROOT, config_file)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    got = control.readings(config, mix, 2**31 + 5, queries=1)
    assert got["forward_mismatches"] > check.LIMITS["forward_mismatches"]
    weights = model.weights(config, 2**31 + 5)
    x = model.query(config, 2**31 + 5, 0, 0)
    ref = reference.forward(config["block"], weights, x)
    assert check.forward_mismatches(ref, ref) == 0
    assert check.forward_mismatches(None, ref) == got["entries"] // \
        int(mix["clients"])


def test_queries_and_weights_follow_the_seed():
    config = _config("d16")
    a, b = model.weights(config, 5), model.weights(config, 5)
    assert all(np.array_equal(a[i][k], b[i][k]) for i in range(2) for k in a[0])
    assert not np.array_equal(model.query(config, 5, 0, 0),
                              model.query(config, 6, 0, 0))
    q = model.query(config, 2**33 + 7, 1, 0)
    assert q.shape == (16, 8) and not q[16:].any()
