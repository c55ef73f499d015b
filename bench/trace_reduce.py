"""Reduction of one profiler trace to the device's busy time, idle gaps
and custom-call (Pallas) time.

The trace is JAX's ``.xplane.pb``, read through ``jax.profiler.ProfileData``
(``load``).  Device operations are the events of each device plane's
``XLA Ops`` line.  A run off the chip has no device plane; there the
XLA:CPU executor threads of the host plane stand in for it, so the
harness can be rehearsed end to end.  All times are in the trace's own
nanoseconds, relative to the profile's start.

* busy: the union of the operation intervals inside the window, per
  chip, averaged over the chips;
* idle gaps: the holes in that union, each labelled with what the host
  was doing then (``label_gaps``, from host stack samples);
* Pallas time and bytes: operations whose HLO is a ``tpu_custom_call``,
  with the least bytes each must move (``work.hlo_bytes``);
* device ops: time per (jitted entry, operation kind), the entry being
  the ``XLA Modules`` event that contains the operation.

On the TPU an ``XLA Ops`` event's name is the operation's HLO text
(``%fusion.3 = u32[8,128]{...} fusion(...)``), which gives its kind and,
for a custom call, its operand and result shapes.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Sequence, Tuple

import work

_CHIP = re.compile(r"/device:[A-Z]+:\d+$")
Event = Tuple[float, float, str]            # start, end, name (HLO text)


def load(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def _events(line) -> List[Event]:
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in line.events]


def device_timelines(pd) -> List[Dict[str, List[Event]]]:
    """One ``{"ops": [...], "modules": [...]}`` per chip."""
    chips = []
    for plane in pd.planes:
        if not _CHIP.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = lines.get("XLA Modules")
        chips.append({"ops": _events(lines["XLA Ops"]),
                      "modules": _events(mods) if mods is not None else []})
    if chips:
        return chips
    ops: List[Event] = []        # off the chip: the XLA:CPU executors
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLA"):
                    ops += _events(line)
    return [{"ops": ops, "modules": []}] if ops else []


def profile_start(pd) -> int:
    """The host wall clock (``time.time_ns()``) at the trace's time 0."""
    for plane in pd.planes:
        if plane.name == "Task Environment":
            return int(dict(plane.stats)["profile_start_time"])
    raise ValueError("the trace has no profile_start_time")


def union(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Sorted disjoint union of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def is_pallas(name: str) -> bool:
    """A device op whose HLO is a custom call: on the TPU, a Pallas
    (Mosaic) kernel."""
    return " custom-call(" in name or "tpu_custom_call" in name


_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")


def op_kind(name: str) -> str:
    """``%fusion.12 = u32[8] fusion(...)`` -> ``fusion``."""
    return _stem(name.split(" = ", 1)[0].lstrip("%"))


def _stem(name: str) -> str:
    return _SUFFIX.sub("", name)


def _entry(mods: List[Event], starts: List[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][0] <= t <= mods[i][1]:
        return _stem(mods[i][2])
    return "?"


def label_gaps(idle: Sequence[Tuple[float, float]],
               samples: Sequence[Tuple[float, str]], top: int = 10
               ) -> List[List]:
    """The ``top`` longest gaps, longest first, as ``[label, seconds]``;
    the label is the host activity sampled most often inside the gap."""
    times = [t for t, _ in samples]
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        i, j = bisect.bisect_left(times, s), bisect.bisect_right(times, e)
        seen = collections.Counter(lab for _, lab in samples[i:j])
        label = seen.most_common(1)[0][0] if seen else "unsampled"
        out.append([label, (e - s) * 1e-9])
    return out


def reduce(pd, window: Tuple[float, float],
           samples: Sequence[Tuple[float, str]] = (), top: int = 10) -> Dict:
    """Busy and idle time, Pallas time and bytes, and the breakdown, of
    ``window`` (trace nanoseconds); ``samples`` are host activity labels
    at trace times."""
    chips = device_timelines(pd)
    if not chips:
        return {}
    lo, hi = window
    busy_total = pallas_ns = 0.0
    pallas_bytes = 0
    per_op: Dict[str, float] = collections.defaultdict(float)
    kinds: Dict[str, Tuple[str, object]] = {}
    idle_all: List[Tuple[float, float]] = []
    for chip in chips:
        ops = [ev for ev in chip["ops"] if ev[1] > lo and ev[0] < hi]
        busy = union([(ev[0], ev[1]) for ev in ops], lo, hi)
        busy_total += sum(e - s for s, e in busy)
        idle_all += gaps(busy, lo, hi)
        mods = sorted(chip["modules"])
        starts = [m[0] for m in mods]
        for ev in ops:
            dur = min(ev[1], hi) - max(ev[0], lo)
            if ev[2] not in kinds:          # an op's HLO repeats in loops
                kinds[ev[2]] = ("pallas:" + op_kind(ev[2]),
                                work.hlo_bytes(ev[2])) \
                    if is_pallas(ev[2]) else (op_kind(ev[2]), None)
            kind, nbytes = kinds[ev[2]]
            if nbytes is not None:
                pallas_ns += dur
                pallas_bytes += nbytes
            per_op[f"{_entry(mods, starts, (ev[0] + ev[1]) / 2)}/{kind}"] \
                += dur
    n = len(chips)
    samples = sorted(samples)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n * 1e-9,
        "pallas_s": pallas_ns / n * 1e-9,
        "pallas_bytes": pallas_bytes / n,
        "device_ops": [[k, v * 1e-9] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": label_gaps(idle_all, samples, top),
    }
