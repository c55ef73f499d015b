"""The control of ``correct``: the reference at the step below the stated
precision, put in the program's place.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--queries 8]

For each seed it makes the weights and the first ``--queries`` queries of
every client of the cell, as a run with that seed sends them, computes
the boundary activations with int8 operands (``reference.forward(...,
bits=8)``) and prints, as one JSON line per seed, the number
``forward_mismatches`` then reads against the reference: the count is
``check.forward_mismatches``, the harness's own.  The limit is 0, so
every reading has to be above it.  Host numpy only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
import check  # noqa: E402
import model  # noqa: E402
import reference  # noqa: E402


def readings(config: dict, traffic: dict, seed: int, queries: int) -> dict:
    weights = model.weights(config, seed)
    block = config["block"]
    n = entries = 0
    for c in range(int(traffic["clients"])):
        for i in range(queries):
            x = model.query(config, seed, c, i)
            ref = reference.forward(block, weights, x)
            ctl = reference.forward(block, weights, x, bits=8)
            n += check.forward_mismatches(ctl, ref)
            entries += sum(a.size for a in ref)
    return {"seed": seed, "forward_mismatches": n, "entries": entries}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--queries", type=int, default=8)
    a = p.parse_args(argv)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    w = {x["name"]: x for x in bench["workloads"]}[a.workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(os.path.dirname(BENCH), conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    for seed in a.seeds:
        print(json.dumps({"workload": a.workload,
                          **readings(config, traffic, seed, a.queries)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
