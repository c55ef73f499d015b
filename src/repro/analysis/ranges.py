"""Jaxpr interval/overflow analyzer (abstract interpretation).

Proves, for every kernel entry point registered in ``kernels/ops.py``
(``ANALYSIS_ENTRIES``) and every field primitive in ``core/field.py``
(``ANALYSIS_BOUNDS``), that no integer intermediate can exceed its dtype
under the declared input bounds — the hand-written ``# < 2P, no uint32
overflow`` comments become machine-checked facts.

How it works
------------
Each entry is traced to a jaxpr with its inputs bounded as declared
(Fp < P, full-range u32, ...).  The analyzer walks the equations
propagating ``[lo, hi]`` intervals computed with exact Python ints, so an
``add``/``mul``/``shift_left`` whose mathematical result can exceed the
dtype max is a finding.  Two deliberate wrap idioms are modeled instead
of flagged:

* Montgomery reduction multiplies by ``-P^-1 mod 2^32`` — multiplies by a
  literal in ``field.WRAP_OK_CONSTANTS`` may wrap silently.
* The guarded-subtract pattern ``where(a >= b, a - b, ...)`` — a uint
  ``sub`` that can underflow yields the full dtype range *plus symbolic
  provenance*, and ``select_n`` re-derives the tight per-branch interval
  from the comparison that guards it (``_refine_case``).  An unguarded
  wrapping subtract therefore propagates [0, 2^32) and trips the
  downstream overflow / declared-output checks.

Structured control flow is interpreted, not approximated away: ``pjit``
recurses, ``scan``/``while`` iterate the carry to a join fixpoint,
``cond`` joins feasible branches, and ``pallas_call`` runs the kernel
body over abstract Ref cells (weak updates, read-after-join) to a
fixpoint — grid semantics of the accumulate-in-VMEM kernels are covered,
not just their pure-jnp twins.  Unknown primitives on integer data are
hard findings: coverage gaps must be visible, never silently unsound.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import field as F
from repro.kernels import ops as KOPS

from . import AnalysisError, Finding

_MAX_LOOP_ITERS = 80      # scan/while carry-fixpoint budget
_MAX_BODY_ITERS = 12      # pallas grid-body fixpoint budget

KIND_RANGE = {
    "fp": (0, F.P - 1),
    "u32": (0, 2**32 - 1),
}


class AbsVal:
    """Interval [lo, hi] (exact ints; None,None = untracked/float) plus
    optional symbolic provenance used by select_n refinement."""
    __slots__ = ("lo", "hi", "expr")

    def __init__(self, lo, hi, expr=None):
        self.lo, self.hi, self.expr = lo, hi, expr

    @property
    def tracked(self) -> bool:
        return self.lo is not None

    @property
    def const(self):
        return self.lo if (self.lo is not None and self.lo == self.hi) else None

    def __repr__(self):
        return f"AbsVal[{self.lo}, {self.hi}]"


TOP = AbsVal(None, None)


def _join(a: AbsVal, b: AbsVal) -> AbsVal:
    if a is b:
        return a
    if not (a.tracked and b.tracked):
        return TOP
    return AbsVal(min(a.lo, b.lo), max(a.hi, b.hi))


def _same(a: AbsVal, b: AbsVal) -> bool:
    """Operand match for refinement: identity, or equal constants."""
    return a is b or (a.const is not None and a.const == b.const)


class RefCell:
    """Abstract pallas Ref: None until first write, then a running join."""
    __slots__ = ("val",)

    def __init__(self, val: Optional[AbsVal] = None):
        self.val = val


def _dtype_range(dtype) -> Optional[Tuple[int, int]]:
    if dtype == jnp.bool_ or dtype == np.bool_:
        return (0, 1)
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        return (int(info.min), int(info.max))
    return None


def _from_concrete(v) -> AbsVal:
    arr = np.asarray(v)
    if _dtype_range(arr.dtype) is None:
        return TOP
    if arr.size == 0:
        return AbsVal(0, 0)
    return AbsVal(int(arr.min()), int(arr.max()))


class Analyzer:
    def __init__(self, entry: str, findings: List[Finding]):
        self.entry = entry
        self.findings = findings
        self.grid: Tuple[int, ...] = ()
        self.cells: List[RefCell] = []

    # -- reporting ----------------------------------------------------------
    def _where(self, eqn) -> str:
        loc = ""
        try:
            from jax._src import source_info_util
            loc = source_info_util.summarize(eqn.source_info)
        except Exception:
            pass
        return f"{self.entry}: {eqn.primitive.name}" + (f" @ {loc}" if loc else "")

    def _flag(self, category: str, eqn, detail: str):
        self.findings.append(
            Finding("ranges", category, self._where(eqn), detail))

    # -- jaxpr walking ------------------------------------------------------
    def run_closed(self, closed, args: Sequence[AbsVal]) -> List[AbsVal]:
        consts = [_from_concrete(c) for c in closed.consts]
        return self.run_jaxpr(closed.jaxpr, consts, args)

    def run_jaxpr(self, jaxpr, consts: Sequence[AbsVal],
                  args: Sequence[AbsVal]) -> List[AbsVal]:
        env: Dict = {}

        def read(atom):
            if isinstance(atom, jax.extend.core.Literal):
                return _from_concrete(atom.val)
            return env[atom]

        assert len(jaxpr.constvars) == len(consts), self.entry
        assert len(jaxpr.invars) == len(args), \
            f"{self.entry}: arity {len(jaxpr.invars)} != {len(args)}"
        for var, val in zip(jaxpr.constvars, consts):
            env[var] = val
        for var, val in zip(jaxpr.invars, args):
            env[var] = val
        for eqn in jaxpr.eqns:
            outs = self.eqn(eqn, [read(x) for x in eqn.invars], env)
            assert len(outs) == len(eqn.outvars), \
                f"{self.entry}: {eqn.primitive.name} out arity"
            for var, val in zip(eqn.outvars, outs):
                if type(var).__name__ != "DropVar":
                    env[var] = val
        return [read(v) for v in jaxpr.outvars]

    def eqn(self, eqn, ins: List, env: Dict) -> List:
        name = eqn.primitive.name
        handler = getattr(self, "p_" + name.replace("-", "_"), None)
        if handler is not None:
            return handler(eqn, ins)
        # generic fallbacks keyed by behavior class
        if name in _PASS_THROUGH:
            return [ins[0]]
        if name in _JOIN_ALL:
            out = ins[0]
            for v in ins[1:]:
                out = _join(out, v)
            return [out]
        if all(_dtype_range(v.aval.dtype) is None for v in eqn.outvars):
            return [TOP] * len(eqn.outvars)   # pure float math: untracked
        self._flag("analyzer-coverage", eqn,
                   f"unhandled primitive '{name}' on integer data — "
                   "extend repro.analysis.ranges before trusting this entry")
        return [self._clamped_top(v) for v in eqn.outvars]

    @staticmethod
    def _clamped_top(outvar) -> AbsVal:
        rng = _dtype_range(outvar.aval.dtype)
        return TOP if rng is None else AbsVal(rng[0], rng[1])

    # -- integer arithmetic -------------------------------------------------
    def _int_out(self, eqn, lo: int, hi: int, expr=None,
                 wrap_ok: bool = False) -> AbsVal:
        rng = _dtype_range(eqn.outvars[0].aval.dtype)
        if rng is None:
            return TOP
        dlo, dhi = rng
        if lo < dlo or hi > dhi:
            if not wrap_ok:
                self._flag(
                    "u32-overflow" if dlo == 0 else "int-overflow", eqn,
                    f"interval [{lo}, {hi}] exceeds {eqn.outvars[0].aval.dtype}"
                    f" range [{dlo}, {dhi}]")
            return AbsVal(dlo, dhi, expr)
        return AbsVal(lo, hi, expr)

    def p_add(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        expr = None
        if b.const is not None:
            expr = ("addc", a, b.const)
        elif a.const is not None:
            expr = ("addc", b, a.const)
        return [self._int_out(eqn, a.lo + b.lo, a.hi + b.hi, expr)]

    def p_sub(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        rng = _dtype_range(eqn.outvars[0].aval.dtype)
        lo, hi = a.lo - b.hi, a.hi - b.lo
        if rng and rng[0] == 0 and lo < 0:
            # possibly-wrapping unsigned subtract: the guarded-where idiom.
            # Full range now; select_n re-derives the branch interval.
            return [AbsVal(rng[0], rng[1], ("sub", a, b))]
        return [self._int_out(eqn, lo, hi, ("sub", a, b))]

    def p_mul(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        wrap_ok = (a.const in F.WRAP_OK_CONSTANTS
                   or b.const in F.WRAP_OK_CONSTANTS)
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        return [self._int_out(eqn, min(prods), max(prods), wrap_ok=wrap_ok)]

    def p_integer_pow(self, eqn, ins):
        a, = ins
        p = eqn.params["y"]
        if not a.tracked:
            return [self._clamped_top(eqn.outvars[0])]
        vals = [a.lo**p, a.hi**p]
        return [self._int_out(eqn, min(vals + [0] if p % 2 else vals),
                              max(vals))]

    def p_shift_left(self, eqn, ins):
        a, s = ins
        if not (a.tracked and s.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        return [self._int_out(eqn, a.lo << s.lo, a.hi << s.hi)]

    def p_shift_right_logical(self, eqn, ins):
        a, s = ins
        if not (a.tracked and s.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        return [AbsVal(a.lo >> s.hi, a.hi >> s.lo)]

    p_shift_right_arithmetic = p_shift_right_logical

    def p_and(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        if a.lo < 0 or b.lo < 0:
            return [self._clamped_top(eqn.outvars[0])]
        return [AbsVal(0, min(a.hi, b.hi))]

    def p_or(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked) or a.lo < 0 or b.lo < 0:
            return [self._clamped_top(eqn.outvars[0])]
        bits = max(a.hi.bit_length(), b.hi.bit_length())
        return [AbsVal(max(a.lo, b.lo), (1 << bits) - 1)]

    def p_xor(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked) or a.lo < 0 or b.lo < 0:
            return [self._clamped_top(eqn.outvars[0])]
        bits = max(a.hi.bit_length(), b.hi.bit_length())
        return [AbsVal(0, (1 << bits) - 1)]

    def p_rem(self, eqn, ins):
        a, b = ins
        if not b.tracked or b.lo <= 0:
            return [self._clamped_top(eqn.outvars[0])]
        return [AbsVal(0, b.hi - 1)]

    def p_div(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked) or a.lo < 0 or b.lo <= 0:
            return [self._clamped_top(eqn.outvars[0])]
        return [AbsVal(a.lo // b.hi, a.hi // b.lo)]

    def p_max(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        return [AbsVal(max(a.lo, b.lo), max(a.hi, b.hi))]

    def p_min(self, eqn, ins):
        a, b = ins
        if not (a.tracked and b.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        return [AbsVal(min(a.lo, b.lo), min(a.hi, b.hi))]

    def p_clamp(self, eqn, ins):
        lo_v, x, hi_v = ins
        if not (lo_v.tracked and x.tracked and hi_v.tracked):
            return [self._clamped_top(eqn.outvars[0])]
        return [AbsVal(max(x.lo, lo_v.lo), min(x.hi, hi_v.hi))]

    def p_neg(self, eqn, ins):
        a, = ins
        rng = _dtype_range(eqn.outvars[0].aval.dtype)
        if rng is None or not a.tracked:
            return [self._clamped_top(eqn.outvars[0])]
        if rng[0] == 0 and a.hi > 0:     # unsigned negate wraps
            return [AbsVal(rng[0], rng[1])]
        return [self._int_out(eqn, -a.hi, -a.lo)]

    # -- comparisons (bool out, provenance for refinement) ------------------
    def _cmp(self, eqn, ins, tag):
        a, b = ins
        return [AbsVal(0, 1, (tag, a, b))]

    def p_ge(self, eqn, ins):
        return self._cmp(eqn, ins, "ge")

    def p_gt(self, eqn, ins):
        return self._cmp(eqn, ins, "gt")

    def p_le(self, eqn, ins):
        return self._cmp(eqn, ins, "le")

    def p_lt(self, eqn, ins):
        return self._cmp(eqn, ins, "lt")

    def p_eq(self, eqn, ins):
        return self._cmp(eqn, ins, "eq")

    def p_ne(self, eqn, ins):
        return self._cmp(eqn, ins, "ne")

    # -- select_n with guarded-branch refinement ----------------------------
    def p_select_n(self, eqn, ins):
        pred, *cases = ins
        if len(cases) != 2 or pred.expr is None or pred.expr[0] not in (
                "ge", "eq"):
            out = cases[0]
            for c in cases[1:]:
                out = _join(out, c)
            return [out]
        refined = [self._refine_case(pred.expr, cases[0], branch=False),
                   self._refine_case(pred.expr, cases[1], branch=True)]
        return [_join(refined[0], refined[1])]

    @staticmethod
    def _refine_case(pred_expr, val: AbsVal, branch: bool) -> AbsVal:
        """Tighten a select_n case interval using the guarding comparison.

        Handles the three field.py idioms (fadd/fmul reduce, fsub borrow,
        fneg) exactly; anything else keeps its unrefined interval, which
        is always sound.
        """
        tag, x, y = pred_expr
        if not (x.tracked and y.tracked and val.tracked):
            return val
        if tag == "ge" and branch:
            # x >= y holds; val == x - y gives [max(0, lo), hi] exactly
            if val.expr and val.expr[0] == "sub" and \
                    _same(val.expr[1], x) and _same(val.expr[2], y):
                return AbsVal(max(0, x.lo - y.hi), max(0, x.hi - y.lo))
            return val
        if tag == "ge" and not branch:
            # x < y holds
            if _same(val, x):                       # val == x: x <= hi(y)-1
                return AbsVal(x.lo, min(x.hi, y.hi - 1))
            if val.expr and val.expr[0] == "sub" and _same(val.expr[2], y):
                c = val.expr[1]                     # val == c - y, c == x + K
                if c.expr and c.expr[0] == "addc" and _same(c.expr[1], x):
                    k = c.expr[2]                   # x < y: val <= K - 1
                    return AbsVal(max(val.lo, k + x.lo - y.hi),
                                  min(k - 1, c.hi - y.lo))
            return val
        if tag == "eq":
            zero = y.const == 0
            if branch and zero and _same(val, x):   # x == 0: val == x == 0
                return AbsVal(0, 0)
            if not branch and zero and val.expr and val.expr[0] == "sub" \
                    and _same(val.expr[2], x):
                k = val.expr[1]                     # val == K - x with x >= 1
                if k.const is not None:
                    return AbsVal(k.const - x.hi,
                                  k.const - max(x.lo, 1))
            return val
        return val

    # -- shape/data movement ------------------------------------------------
    def p_concatenate(self, eqn, ins):
        out = ins[0]
        for v in ins[1:]:
            out = _join(out, v)
        return [out]

    def p_pad(self, eqn, ins):
        return [_join(ins[0], ins[1])]

    def p_iota(self, eqn, ins):
        dim = eqn.params["dimension"]
        n = eqn.params["shape"][dim]
        return [AbsVal(0, max(0, n - 1))]

    def p_convert_element_type(self, eqn, ins):
        a, = ins
        rng = _dtype_range(eqn.outvars[0].aval.dtype)
        if rng is None:
            return [TOP]
        if not a.tracked:
            return [AbsVal(rng[0], rng[1])]
        if a.lo < rng[0] or a.hi > rng[1]:
            self._flag("convert-overflow", eqn,
                       f"[{a.lo}, {a.hi}] does not fit "
                       f"{eqn.outvars[0].aval.dtype}")
            return [AbsVal(rng[0], rng[1])]
        return [AbsVal(a.lo, a.hi, a.expr)]

    def p_reduce_sum(self, eqn, ins):
        a, = ins
        if not a.tracked:
            return [self._clamped_top(eqn.outvars[0])]
        shape = eqn.invars[0].aval.shape
        n = 1
        for ax in eqn.params["axes"]:
            n *= shape[ax]
        return [self._int_out(eqn, n * a.lo, n * a.hi)]

    def p_reduce_max(self, eqn, ins):
        return [ins[0]]

    p_reduce_min = p_reduce_max

    def p_reduce_and(self, eqn, ins):
        return [AbsVal(0, 1)]

    p_reduce_or = p_reduce_and

    def p_dot_general(self, eqn, ins):
        a, b = ins
        rng = _dtype_range(eqn.outvars[0].aval.dtype)
        if rng is None:
            return [TOP]
        if not (a.tracked and b.tracked):
            return [AbsVal(rng[0], rng[1])]
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        k = 1
        for ax in lhs_c:
            k *= eqn.invars[0].aval.shape[ax]
        prods = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        return [self._int_out(eqn, k * min(prods), k * max(prods))]

    # -- structured control flow --------------------------------------------
    def p_pjit(self, eqn, ins):
        return self.run_closed(eqn.params["jaxpr"], ins)

    p_jit = p_pjit          # the primitive's name from JAX 0.7 on

    def p_roll(self, eqn, ins):
        # a lane rotation keeps the interval but moves elements: a fresh
        # value, so select_n refinement never pairs it with its source
        return [AbsVal(ins[0].lo, ins[0].hi)]

    def p_custom_jvp_call(self, eqn, ins):
        return self.run_closed(eqn.params["call_jaxpr"], ins)

    def p_custom_vjp_call(self, eqn, ins):
        return self.run_closed(eqn.params["call_jaxpr"], ins)

    def p_scan(self, eqn, ins):
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        closed = eqn.params["jaxpr"]
        consts, carry, xs = ins[:nc], list(ins[nc:nc + ncar]), ins[nc + ncar:]
        ys_join: Optional[List[AbsVal]] = None
        if eqn.params["length"] <= _MAX_LOOP_ITERS:
            # short static trip count: interpret every iteration in order —
            # exact for loop counters (a fori_loop index indexing a table)
            for _it in range(eqn.params["length"]):
                outs = self.run_closed(closed, consts + carry + list(xs))
                carry, ys = list(outs[:ncar]), outs[ncar:]
                ys_join = ys if ys_join is None else [
                    _join(a, b) for a, b in zip(ys_join, ys)]
            if ys_join is None:
                ys_join = [self._clamped_top(v) for v in eqn.outvars[ncar:]]
            return carry + ys_join
        for _it in range(_MAX_LOOP_ITERS):
            outs = self.run_closed(closed, consts + carry + list(xs))
            new_carry, ys = outs[:ncar], outs[ncar:]
            ys_join = ys if ys_join is None else [
                _join(a, b) for a, b in zip(ys_join, ys)]
            joined = [_join(c, n) for c, n in zip(carry, new_carry)]
            if all(j.lo == c.lo and j.hi == c.hi
                   for j, c in zip(joined, carry)):
                return joined + ys_join
            carry = joined
        self._flag("loop-divergence", eqn,
                   "scan carry interval did not stabilize in "
                   f"{_MAX_LOOP_ITERS} iterations — unbounded growth?")
        widened = [self._clamped_top(v) for v in eqn.outvars[:ncar]]
        outs = self.run_closed(closed, list(consts) + widened + list(xs))
        return widened + [_join(a, b) for a, b in zip(ys_join, outs[ncar:])]

    def p_while(self, eqn, ins):
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        body = eqn.params["body_jaxpr"]
        bconsts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        for _it in range(_MAX_LOOP_ITERS):
            outs = self.run_closed(body, list(bconsts) + carry)
            joined = [_join(c, n) for c, n in zip(carry, outs)]
            if all(j.lo == c.lo and j.hi == c.hi
                   for j, c in zip(joined, carry)):
                return joined
            carry = joined
        self._flag("loop-divergence", eqn,
                   "while carry interval did not stabilize")
        return [self._clamped_top(v) for v in eqn.outvars]

    def p_cond(self, eqn, ins):
        index, *args = ins
        branches = eqn.params["branches"]
        feasible = range(len(branches))
        if index.tracked:
            feasible = [i for i in feasible
                        if index.lo <= i <= index.hi]
        snap = [c.val for c in self.cells]
        branch_cells: List[List[Optional[AbsVal]]] = []
        branch_outs = []
        for i in feasible:
            for c, v in zip(self.cells, snap):
                c.val = v
            branch_outs.append(self.run_closed(branches[i], args))
            branch_cells.append([c.val for c in self.cells])
        # join cell effects and outputs across feasible branches
        for ci, cell in enumerate(self.cells):
            vals = [bc[ci] for bc in branch_cells]
            acc = None
            for v in vals:
                if v is None:
                    continue
                acc = v if acc is None else _join(acc, v)
            cell.val = acc
        if not branch_outs or not branch_outs[0]:
            return [TOP] * len(eqn.outvars)
        outs = branch_outs[0]
        for bo in branch_outs[1:]:
            outs = [_join(a, b) for a, b in zip(outs, bo)]
        return outs

    # -- pallas -------------------------------------------------------------
    def p_pallas_call(self, eqn, ins):
        inner = eqn.params["jaxpr"]
        gm = eqn.params.get("grid_mapping")
        grid = tuple(getattr(gm, "grid", ()) or ())
        n_in, n_out = len(eqn.invars), len(eqn.outvars)
        n_scratch = len(inner.invars) - n_in - n_out
        if n_scratch < 0:
            raise AnalysisError(
                f"{self.entry}: pallas_call invar layout unexpected "
                f"({len(inner.invars)} refs for {n_in} ins, {n_out} outs)")
        cells = ([RefCell(v) for v in ins]
                 + [RefCell() for _ in range(n_out + n_scratch)])
        outer_grid, outer_cells = self.grid, self.cells
        self.grid, self.cells = grid, cells
        try:
            consts = [_from_concrete(c) for c in
                      getattr(inner, "consts", ())] or []
            prev = None
            for _it in range(_MAX_BODY_ITERS):
                self.run_jaxpr(inner, consts, cells)
                state = [(c.val.lo, c.val.hi) if c.val is not None
                         and c.val.tracked else c.val for c in cells]
                if state == prev:
                    break
                prev = state
            else:
                self._flag("loop-divergence", eqn,
                           "pallas kernel cell intervals did not stabilize")
        finally:
            self.grid, self.cells = outer_grid, outer_cells
        outs = []
        for i, var in enumerate(eqn.outvars):
            cell = cells[n_in + i]
            if cell.val is None:
                self._flag("uninit-output", eqn,
                           f"pallas output {i} is never written")
                outs.append(self._clamped_top(var))
            else:
                outs.append(cell.val)
        return outs

    def p_program_id(self, eqn, ins):
        axis = eqn.params["axis"]
        if axis < len(self.grid):
            return [AbsVal(0, max(0, self.grid[axis] - 1))]
        return [self._clamped_top(eqn.outvars[0])]

    def p_num_programs(self, eqn, ins):
        axis = eqn.params["axis"]
        if axis < len(self.grid):
            return [AbsVal(self.grid[axis], self.grid[axis])]
        return [self._clamped_top(eqn.outvars[0])]

    def p_get(self, eqn, ins):
        cell = ins[0]
        if not isinstance(cell, RefCell):
            raise AnalysisError(f"{self.entry}: get on non-ref")
        if cell.val is None:
            self._flag("uninit-read", eqn,
                       "read of a Ref before any (joined) write — garbage "
                       "escapes the kernel")
            return [self._clamped_top(eqn.outvars[0])]
        return [cell.val]

    def p_swap(self, eqn, ins):
        cell, new = ins[0], ins[1]
        if not isinstance(cell, RefCell):
            raise AnalysisError(f"{self.entry}: swap on non-ref")
        old = cell.val
        # weak update: other grid steps / branches may observe either value
        cell.val = new if old is None else _join(old, new)
        if old is None:
            return [self._clamped_top(eqn.outvars[0])]
        return [old]


# value-preserving movement: same AbsVal object flows through, keeping the
# identity that select_n refinement matches on
_PASS_THROUGH = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "transpose", "rev", "slice",
    "expand_dims", "copy", "stop_gradient", "gather", "dynamic_slice",
    "reduce_precision", "bitcast_convert_type", "device_put",
})

# conservative join of all integer inputs
_JOIN_ALL = frozenset({
    "dynamic_update_slice", "scatter", "select_and_scatter_add", "sort",
})


def _make_arg(kind: str, shape: Tuple[int, ...]) -> jnp.ndarray:
    if kind not in KIND_RANGE:
        raise AnalysisError(f"unknown bound kind {kind!r}")
    return jnp.zeros(shape, dtype=jnp.uint32)


def analyze_fn(name: str, fn, arg_specs, out_kind: Optional[str]
               ) -> List[Finding]:
    """Trace fn under declared bounds and interval-check its jaxpr."""
    findings: List[Finding] = []
    args = [_make_arg(kind, shape) for kind, shape in arg_specs]
    closed = jax.make_jaxpr(fn)(*args)
    analyzer = Analyzer(name, findings)
    abs_args = [AbsVal(*KIND_RANGE[kind]) for kind, _ in arg_specs]
    outs = analyzer.run_closed(closed, abs_args)
    if out_kind is not None:
        lo, hi = KIND_RANGE[out_kind]
        for i, o in enumerate(outs):
            if not o.tracked:
                findings.append(Finding(
                    "ranges", "untracked-output", name,
                    f"output {i} escaped interval tracking"))
            elif o.lo < lo or o.hi > hi:
                findings.append(Finding(
                    "ranges", f"{out_kind}-range", name,
                    f"output {i} interval [{o.lo}, {o.hi}] exceeds declared "
                    f"{out_kind} bound [{lo}, {hi}]"))
    return findings


def _covered_ops_entry_points() -> List[str]:
    """Public kernel entry wrappers in ops.py that must appear in the
    registry — coverage is asserted, not assumed."""
    import inspect
    skip = {"kernel_path", "thread_path", "use_fused", "on_tpu"}
    out = []
    for nm, obj in vars(KOPS).items():
        if (not nm.startswith("_") and nm not in skip
                and inspect.isfunction(obj) and obj.__module__ == KOPS.__name__):
            out.append(nm)
    return out


def run() -> List[Finding]:
    findings: List[Finding] = []
    entries = dict(KOPS.ANALYSIS_ENTRIES)
    missing = [nm for nm in _covered_ops_entry_points()
               if not any(k == nm or k.startswith(nm + "_") for k in entries)]
    if missing:
        raise AnalysisError(
            f"kernel entry points missing ANALYSIS_ENTRIES bounds: {missing}")
    for nm, spec in list(F.ANALYSIS_BOUNDS.items()) + list(entries.items()):
        findings.extend(analyze_fn(nm, spec["fn"], spec["args"], spec["out"]))
    return findings
