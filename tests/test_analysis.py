"""Tests for the repro.analysis soundness static-analysis package.

Three tiers:

* interval-domain unit tests (``ranges.AbsVal`` / ``analyze_fn`` on
  tiny synthetic functions with known-good and known-bad ranges);
* clean-tree gates — every analysis pass must report zero findings on
  the repository as it stands (this is exactly the blocking CI check);
* the seeded-bug mutation corpus — each of the >=6 mutants must be
  caught by its analysis, proving the linters see their bug class.

The fs/tape clean-tree tests share one recorded golden prove via a
session fixture so the suite pays the prover cost once.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import Finding, ranges
from repro.analysis.ranges import AbsVal, TOP, _from_concrete, _join, analyze_fn
from repro.core import field as F


# ---------------------------------------------------------------------------
# interval domain units
# ---------------------------------------------------------------------------
def test_absval_join_and_const():
    a, b = AbsVal(2, 5), AbsVal(4, 9)
    j = _join(a, b)
    assert (j.lo, j.hi) == (2, 9)
    assert _join(a, TOP) is TOP
    assert AbsVal(7, 7).const == 7 and AbsVal(2, 5).const is None
    assert not TOP.tracked


def test_from_concrete():
    v = _from_concrete(np.array([3, 11, 5], dtype=np.uint32))
    assert (v.lo, v.hi) == (3, 11)
    assert not _from_concrete(np.array([1.5])).tracked   # float: untracked


def test_analyze_fn_clean_add():
    # conditional-subtract add stays inside [0, P-1]
    findings = analyze_fn("t_add", F.fadd,
                          [("fp", (8,)), ("fp", (8,))], "fp")
    assert findings == []


def test_analyze_fn_flags_unreduced_add():
    # a + b on two field elements reaches 2P-2 > P-1
    def bad(a, b):
        return a + b
    findings = analyze_fn("t_bad_add", bad,
                          [("fp", (8,)), ("fp", (8,))], "fp")
    assert any(f.category == "fp-range" for f in findings), findings


def test_analyze_fn_flags_u32_mul_overflow():
    # (P-1)^2 >> 2^32 - 1: the raw product must be flagged at the eqn
    def bad(a, b):
        return a * b
    findings = analyze_fn("t_bad_mul", bad,
                          [("fp", (8,)), ("fp", (8,))], None)
    assert any(f.category == "u32-overflow" for f in findings), findings


def test_analyze_fn_limb_product_clean():
    # 16-bit limb products stay under 2^32: the idiom field.py relies on
    def limb_mul(a, b):
        return (a & jnp.uint32(0xFFFF)) * (b & jnp.uint32(0xFFFF))
    findings = analyze_fn("t_limb_mul", limb_mul,
                          [("u32", (8,)), ("u32", (8,))], None)
    assert findings == []


def test_ranges_registry_covers_ops_entry_points():
    from repro.kernels import ops as KOPS
    entries = dict(KOPS.ANALYSIS_ENTRIES)
    for nm in ranges._covered_ops_entry_points():
        assert any(k == nm or k.startswith(nm + "_") for k in entries), \
            f"ops.py entry point {nm} has no declared analysis bounds"


# ---------------------------------------------------------------------------
# clean-tree gates (what CI blocks on)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="session")
def golden_log():
    from repro.analysis.replay import run_golden_prove
    return run_golden_prove()


def test_ranges_clean_tree():
    assert ranges.run() == []


def test_locks_clean_tree():
    from repro.analysis import locks
    assert locks.run() == []


def test_fs_clean_tree(golden_log):
    from repro.analysis import fs_lint
    assert fs_lint.ast_checks() == []
    assert fs_lint.replay_checks(golden_log) == []


def test_tape_clean_tree(golden_log):
    from repro.analysis import tape_lint
    assert tape_lint.replay_checks(golden_log) == []


@pytest.fixture(scope="session")
def multi_head_log():
    """The golden prove at 4 heads: the attention matmuls take the
    argument batched over heads, which the 1-head prove never enters."""
    from repro.analysis.replay import run_golden_prove
    return run_golden_prove(heads=4)


def test_fs_clean_multi_head(multi_head_log):
    from repro.analysis import fs_lint
    assert fs_lint.replay_checks(multi_head_log) == []


def test_tape_clean_multi_head(multi_head_log):
    from repro.analysis import tape_lint
    assert tape_lint.replay_checks(multi_head_log) == []


def test_golden_log_sees_the_prover(golden_log):
    # the replay harness must actually observe a prover, or every
    # replay check would pass vacuously
    kinds = {ev.kind for ev in golden_log.events}
    assert {"absorb", "squeeze", "commit", "leaf_claim",
            "open", "finalize"} <= kinds
    assert any(ev.prover for ev in golden_log.events)


# ---------------------------------------------------------------------------
# seeded-bug corpus: each mutant must be caught
# ---------------------------------------------------------------------------
def _mutants():
    from repro.analysis.mutants import MUTANTS
    assert len(MUTANTS) >= 6
    return MUTANTS


@pytest.mark.parametrize("name", [m.name for m in _mutants()])
def test_mutant_is_caught(name):
    from repro.analysis.mutants import MUTANTS, run_mutant
    m, = [m for m in MUTANTS if m.name == name]
    r = run_mutant(m)
    assert r.detected, (
        f"mutant {m.name} ({m.description}) not flagged by {m.analysis}; "
        f"findings: {[str(f) for f in r.findings][:10]}")
    # and the finding is of the expected class, not collateral noise
    assert any(f.analysis == m.analysis and f.category in m.expect
               for f in r.findings)


def test_finding_str_roundtrip():
    f = Finding("fs", "dropped-absorb", "transcript[x]@3", "detail")
    assert "fs:dropped-absorb" in str(f) and "transcript[x]@3" in str(f)
