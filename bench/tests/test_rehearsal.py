"""The harness end to end on the CPU, at a toy configuration and traffic
that exist only for this test and are not cells.

The toy cell lives in a copy of the benchmark (``BENCHMARK.json`` and
``bench/``) under ``.bench_out/rehearsal``, with the program's ``src``
linked beside it: its configuration, traffic and one extra metric are
new files plus entries in that copy's ``BENCHMARK.json``, and nothing
else is edited, so the harness has to find them by name.  The copy's
compile cache stays there between sessions.  A run here replaces only
the harness's look for a chip; one test each breaks the timed path
underneath, from outside the harness (an activation altered where the
prover's forward replay produces it, the replay's activations replaced
by the control's, a byte of an attestation altered on the way), and
sees ``correct`` come out false.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
TOY = os.path.join(ROOT, ".bench_out", "rehearsal")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]
CELL = "toy.pair"


def _toy_root() -> str:
    bench_dst = os.path.join(TOY, "bench")
    shutil.rmtree(bench_dst, ignore_errors=True)
    shutil.copytree(os.path.join(ROOT, "bench"), bench_dst,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    if not os.path.exists(os.path.join(TOY, "src")):
        os.symlink(os.path.join(ROOT, "src"), os.path.join(TOY, "src"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "bench", "configs",
                           "nanozk-d128-2L.json")) as f:
        config = json.load(f)
    config.update(name="toy-d16", pcs_queries=2,
                  block={"d": 16, "dff": 32, "heads": 2, "dh": 8, "seq": 8})
    files = {
        "configs/toy-d16.json": json.dumps(config),
        "traffic/toy-pair.json": json.dumps(
            {"clients": 2, "loop": "closed", "policy": {"budget": 1.0},
             "gateway": {"max_batch": 4, "window_seconds": 0.05},
             "service": {"workers": 1}}),
        "metrics/toy.queries.py":
            "def read(rec):\n    return len(rec['queries'])\n",
    }
    for name, text in files.items():
        with open(os.path.join(bench_dst, name), "w") as f:
            f.write(text)
    bench["configs"].append({"name": "toy-d16", "source": "test",
                             "file": "bench/configs/toy-d16.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"] = [{"name": CELL, "config": "toy-d16",
                           "traffic": "toy-pair", "chips": 1,
                           "why": "rehearsal"}]
    for m in bench["per_layer"]:
        m["workloads"] = [CELL]
    bench["per_layer"].append({"name": "toy.queries", "unit": "queries",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "tta_p50_s",
                               "workloads": [CELL]})
    with open(os.path.join(TOY, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return TOY


@pytest.fixture(scope="module")
def harness():
    pytest.importorskip("jax")
    root = _toy_root()
    spec = importlib.util.spec_from_file_location(
        "bench_run_rehearsal", os.path.join(root, "bench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    peaks = mod.load_json(os.path.join(root, "bench", "peaks.json"))

    def on_cpu(chips):
        import jax
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices()), "peaks": peaks["TPU v5 lite"]}

    def run(seed, trace=False, patch=None, seconds=1.0):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(mod, "device_info", on_cpu)
            if patch is not None:
                patch(mp, mod)
            out = mod.run(CELL, seed, seconds, trace, root=root)
        json.loads(json.dumps(out))        # the line is plain JSON
        return out
    return run


def _in_window(mp, mod, cls, name, alter):
    """Patches ``cls.name`` so that, inside the measured window only, its
    result passes through ``alter(self, args, result)``."""
    live = {"on": False}
    window, inner = mod.serve.Window._window, getattr(cls, name)

    def measured(self, *a, **k):
        live["on"] = True
        try:
            return window(self, *a, **k)
        finally:
            live["on"] = False

    def patched(self, *a, **k):
        out = inner(self, *a, **k)
        return alter(self, a, out) if live["on"] else out
    mp.setattr(mod.serve.Window, "_window", measured)
    mp.setattr(cls, name, patched)


def _answer(mp, mod):
    """An activation altered where the forward replay produces it."""
    from repro.runtime.engine import ProverEngine

    def alter(_engine, _args, fwd):
        fwd.acts[-1] = np.array(fwd.acts[-1], copy=True)
        fwd.acts[-1][0, 0] += 1
        return fwd
    _in_window(mp, mod, ProverEngine, "run_forward", alter)


def _control(seed):
    """The control in the program's place: the forward replay's
    activations are the reference's with int8 operands."""
    def patch(mp, mod):
        import model
        import reference
        from repro.runtime.engine import ProverEngine
        with open(os.path.join(TOY, "bench", "configs", "toy-d16.json")) as f:
            config = json.load(f)
        weights = model.weights(config, seed)

        def alter(_engine, args, fwd):
            ctl = reference.forward(config["block"], weights,
                                    np.asarray(args[0]), bits=8)
            fwd.acts[:] = [np.asarray(c, np.asarray(a).dtype)
                           for a, c in zip(fwd.acts, ctl)]
            return fwd
        _in_window(mp, mod, ProverEngine, "run_forward", alter)
    return patch


def _wire(mp, mod):
    """A byte of each attestation flipped on the way to the client."""
    from repro.gateway import GatewayClient

    def alter(_client, _args, out):
        wire, info = out
        wire = bytearray(wire)
        wire[len(wire) // 2] ^= 1
        return bytes(wire), info
    _in_window(mp, mod, GatewayClient, "attest_bytes", alter)


def test_end_to_end_line(harness):
    out = harness(2**31 + 101)
    assert list(out) == KEYS + ["checks"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2                   # both clients sent
    assert set(out["metrics"]) == {"setup_s", "layer_proofs_per_s",
                                   "tta_p50_s", "verify_ms_per_layer",
                                   "wire_kib_per_layer"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in out["checks"].values())


def test_traced_line_finds_new_files_by_name(harness):
    # long enough for each client's second query, which the trace holds
    out = harness(2**31 + 102, trace=True, seconds=8.0)
    assert list(out) == KEYS + ["breakdown", "checks"]
    assert out["correct"] is True
    m = out["metrics"]
    assert m["toy.queries"]["value"] == out["attempted"]
    for name in ("engine.prove_s_per_layer", "engine.commit_s_per_query",
                 "gateway.queue_wait_s", "device.idle_share", "prove.mfu"):
        assert m[name]["value"] > 0, name
    # no Pallas call runs off the chip: those readers find nothing
    assert "kernels.busy_share" not in m
    assert "kernels.pallas_roofline" not in m
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["device"]["window_s"] > 0.2   # whole attestations, not 0
    b = out["breakdown"]
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


SEED_FAULTS = 2**31 + 103


@pytest.mark.parametrize("patch,counts", [
    (_answer, ("undelivered", "forward_mismatches")),
    (_control(SEED_FAULTS), ("forward_mismatches",)),
    (_wire, ("rejected",)),
], ids=["answer", "control", "wire"])
def test_broken_timed_path_is_not_correct(harness, patch, counts):
    out = harness(SEED_FAULTS, patch=patch)
    # the prover's own relations refuse an altered activation, so that
    # query is never delivered, and the replay differs from the
    # reference; a flipped byte fails the client's check
    assert out["correct"] is False
    for name in counts:
        assert out["checks"][name]["value"] > 0, (name, out["checks"])


def test_no_tpu_no_result(tmp_path):
    """Off a TPU, and in a tree with only the benchmark's files, the
    command exits non-zero and prints nothing on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", "d128-2L.single", "--seed", "1", "--seconds", "1"]
    p = subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    p = subprocess.run([sys.executable, "bench/run.py", *args],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout == ""
