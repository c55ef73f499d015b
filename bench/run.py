"""Benchmark of the attestation service on one TPU chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); every metric is a reader
``bench/metrics/<name>.py``.  The harness finds all three by name.

The run drives the provider's normal path: a ``ProofService`` behind an
``AttestationGateway`` on a localhost socket, with the traffic's
``GatewayClient``s in closed loops in this process.  Set-up (imports,
weights and queries from the seed, the model card's weight commitment
and range proof, the gateway, one warm-up attestation per client and one
host verification) ends where the window starts: at the first measured
query.  No query is sent after ``--seconds``; the window ends when the
last query sent completes.  After it, the clients verify every
attestation on the host CPU (timed), and the harness checks what the
timed path produced (``bench/check.py``).

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the same window under
the profiler.  The last line of stdout is one JSON object; the numbers
compared for ``correct`` are its last key and the last lines of stderr.
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import os
import time


def _process_start() -> float:
    """``time.monotonic()`` at the start of this process (Linux), so
    set-up counts the interpreter's own start too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


T_PROCESS = _process_start()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import check  # noqa: E402
import serve  # noqa: E402


class BenchError(Exception):
    """A run that cannot be measured: no result is printed."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(root: str, workload: str) -> dict:
    """The cell's entry, configuration, traffic and metric lists."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    bench = load_json(path)
    byname = {w["name"]: w for w in bench["workloads"]}
    if workload not in byname:
        raise BenchError(f"unknown workload {workload!r}")
    w = byname[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads",
                                                        [workload])]
    return {"workload": w, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs),
            "peaks": peaks[kind]}


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: str = ROOT) -> dict:
    """One run; returns the result line's object."""
    spec = cell(root, workload)
    dev = device_info(spec["workload"]["chips"])
    config, traffic = spec["config"], spec["traffic"]
    rec = serve.Window(root, config, traffic, seed, log=_log).run(
        seconds, trace, T_PROCESS)
    rec["peaks"] = dev.pop("peaks")
    rec["seed"] = seed
    checks = check.run(rec, config, log=_log)
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = reader(root, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=rec["memory_peak_bytes"])
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values())
           and rec["attempted"] > 0,
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": device}
    if trace:
        t = rec["trace"]
        device.update(busy_s=t.get("busy_s", 0.0),
                      window_s=t.get("window_s", 0.0))
        out["breakdown"] = {"device_ops": t.get("device_ops", []),
                            "idle_gaps": t.get("idle_gaps", [])}
    out["checks"] = checks
    return out


def _log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROCESS:.1f}s] {msg}",
          file=sys.stderr, flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    try:
        out = run(a.workload, a.seed, a.seconds, bool(a.trace))
    except BenchError as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
