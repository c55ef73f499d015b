"""What decides ``correct``: every window attestation, checked after the
window against the plain reference.

* ``undelivered``: queries sent in the window that never got their
  attestation (an error, or the connection lost);
* ``rejected``: attestations the client's verification rejected.  The
  client verifies on the host CPU, on the reference kernel path, as a
  client does on its own machine; each verification is timed here and
  read by ``verify_ms_per_layer``;
* ``forward_mismatches``: boundary activations h_0 .. h_L that the
  prover's forward replay produced for a sent query, delivered or not,
  and that differ from the plain reference (``reference.py``) on the
  same weights and query, counted entry by entry (all of a query's
  entries when its replay was not seen);
* ``root_mismatches``: boundary commitments in an attestation that differ
  from the commitments the reference kernel path makes, on the host CPU,
  of the reference's activations.  This ties the proven statement to
  the reference's answer, and the kernel path's commitments to the
  repo's bit-for-bit oracle.

All four are exact comparisons, so each limit is 0.  The control
(``control.py``) counts ``forward_mismatches`` with the same function.
"""
from __future__ import annotations

import time

import numpy as np

import model
import reference
from serve import forward_key, host_roots, on_host

LIMITS = {"undelivered": 0, "rejected": 0, "forward_mismatches": 0,
          "root_mismatches": 0}


def verify_all(rec: dict) -> None:
    """The clients' verification of every delivered attestation, timed."""
    from repro import api
    for q in rec["queries"]:
        if "wire" not in q:
            continue
        with on_host():
            t = time.monotonic()
            rep = api.verify(q["wire"], q["query"], rec["card"],
                             rec["policy"])
            q["verify_s"] = time.monotonic() - t
        q["ok"] = bool(rep.ok)
        q["layers"] = len(rep.proved_layers or [])
        q["reason"] = rep.reason


def forward_mismatches(seen, ref: list) -> int:
    """Entries of the reference's boundary activations ``ref`` that the
    program's ``seen`` differ in: all of them where ``seen`` is missing
    or of another shape."""
    if seen is None or len(seen) != len(ref):
        return sum(a.size for a in ref)
    return sum(b.size if np.shape(a) != b.shape
               else int(np.count_nonzero(np.asarray(a) != b))
               for a, b in zip(seen, ref))


def run(rec: dict, config: dict, log=print) -> dict:
    from repro import api
    t0 = time.monotonic()
    verify_all(rec)
    weights = model.weights(config, rec["seed"])
    n = {k: 0 for k in LIMITS}
    for q in rec["queries"]:
        ref = reference.forward(config["block"], weights, q["query"])
        n["forward_mismatches"] += forward_mismatches(
            rec["forwards"].get(forward_key(q["query"])), ref)
        if "wire" not in q:
            n["undelivered"] += 1
            continue
        if not q["ok"]:
            n["rejected"] += 1
            log(f"attestation {q['client']}/{q['index']} rejected: "
                f"{q['reason']}")
        try:
            roots = api.Attestation.from_bytes(q["wire"]).proof.boundary_roots
        except Exception:         # noqa: BLE001 — an unreadable attestation
            roots = []
        want = host_roots(rec["block_cfg"], rec["config"], rec["policy"],
                          ref)
        n["root_mismatches"] += sum(
            1 for i, r in enumerate(want)
            if i >= len(roots) or not np.array_equal(np.asarray(roots[i]), r))
    rec["failed"] = n["undelivered"] + n["rejected"]
    log(f"checks of {len(rec['queries'])} attestations took "
        f"{time.monotonic() - t0:.1f}s")
    return {k: {"value": n[k], "limit": LIMITS[k]} for k in LIMITS}
