"""Circuit framework: view algebra + proof context + gadgets.

This realizes the paper's per-layer arithmetic circuit (§3.1, Eq. 2) in
sum-check form. A layer proof is a deterministic SEQUENCE of gadget calls,
executed identically by prover and verifier over a shared Fiat-Shamir
transcript; the prover additionally writes values/sub-proofs to a `tape`
that the verifier consumes in order.

Witness architecture (DESIGN.md §2, "circuit quantization"):
* Every private witness value lives as **8-bit slices** inside one of a few
  PCS commitments (the per-layer aux commitment, the boundary activation
  commitments shared with adjacent layers, and the per-layer weight
  commitment from setup). 16-bit activations are (hi, lo) limb pairs.
* One value-mode LogUp instance per commitment proves ALL of its entries
  are in [0, 256) — this single range check is what pins every committed
  integer exactly, which in turn makes all mod-p gadget relations integer
  relations (every relation's bound is asserted < p/2 at build time).
* Wider quantities (activations, accumulator terms, rescale errors) are
  *virtual*: Affine views over slices. Views evaluate MLEs by linearity,
  so virtual quantities never need their own commitments or openings.

Gadgets reduce every statement to MLE evaluation claims on committed
vectors, which are discharged in one batched PCS opening per commitment at
finalize().
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from . import field as F
from . import lookup as LK
from . import luts as LUTS
from . import pcs as PCS
from . import sumcheck as SC
from .mle import (eq_eval, eq_points, fsum, mle_eval_base,
                  partial_eval_cols, partial_eval_rows)
from .transcript import Transcript

from repro.kernels import ahead as AH
from repro.kernels import ops as KOPS

INV2 = (F.P + 1) // 2    # field inverse of 2 as a canonical int

# Analysis hook (repro.analysis.tape_lint): an observer watching commitment,
# claim, witness-layout and opening events of every live context.  None in
# production — each hook site is one ``is not None`` test.  Events carry the
# ctx so the observer can separate prover from verifier runs.
_OBSERVER = None


def set_observer(observer) -> None:
    """Install (or with None remove) the tape_lint circuit observer."""
    global _OBSERVER
    _OBSERVER = observer


def _notify(event: str, **kw) -> None:
    if _OBSERVER is not None:
        getattr(_OBSERVER, event)(**kw)


@functools.lru_cache(maxsize=None)
def _const_bits_point(idx: int, npfx: int) -> np.ndarray:
    """(npfx, 4) Fp4 point whose rows are the bits of idx, MSB first."""
    out = np.zeros((npfx, 4), np.uint32)
    for j in range(npfx):
        if (idx >> (npfx - 1 - j)) & 1:
            out[j, 0] = F.R_MOD_P
    out.setflags(write=False)
    return out


class ProofError(Exception):
    """Raised by the verifier on any failed check."""


# ---------------------------------------------------------------------------
# View algebra. All views are integer-valued (embedded mod p) vectors of
# length 2^log_n. Claims on views decompose to claims on committed slices.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Slice:
    com: str                  # commitment name
    offset: int               # element offset, multiple of 2^log_n
    log_n: int

    def __post_init__(self):
        assert self.offset % (1 << self.log_n) == 0, "unaligned slice"


@dataclasses.dataclass(frozen=True)
class Affine:
    terms: Tuple[Tuple[int, "View"], ...]   # (field-const coef, view)
    const: int = 0                          # field constant added entrywise
    log_n: Optional[int] = None             # required if terms empty


@dataclasses.dataclass(frozen=True)
class BcastCols:
    """Each element of base repeated 2^extra times (base indexes high bits)."""
    base: "View"
    extra: int


@dataclasses.dataclass(frozen=True)
class BcastRows:
    """Base vector tiled 2^extra times (base indexes low bits)."""
    base: "View"
    extra: int


@dataclasses.dataclass(frozen=True)
class Public:
    """A public integer vector known to both sides (masks, positions)."""
    values: tuple                 # hashable: tuple of ints
    name: str = ""


@dataclasses.dataclass(frozen=True)
class Concat:
    """Concatenation of equal-sized views (e.g. batched LUT witnesses)."""
    parts: Tuple["View", ...]

    def __post_init__(self):
        n = len(self.parts)
        assert n & (n - 1) == 0, "Concat needs a power-of-two part count"
        sizes = {view_log_n(p) for p in self.parts}
        assert len(sizes) == 1, "Concat parts must be equal-sized"


View = Union[Slice, Affine, BcastCols, BcastRows, Public]


def view_log_n(v: View) -> int:
    if isinstance(v, Slice):
        return v.log_n
    if isinstance(v, Affine):
        if v.terms:
            return view_log_n(v.terms[0][1])
        return v.log_n
    if isinstance(v, BcastCols) or isinstance(v, BcastRows):
        return view_log_n(v.base) + v.extra
    if isinstance(v, Concat):
        return view_log_n(v.parts[0]) + (len(v.parts).bit_length() - 1)
    if isinstance(v, Public):
        n = len(v.values)
        ln = n.bit_length() - 1
        assert 1 << ln == n
        return ln
    raise TypeError(v)


def scaled(v: View, c: int) -> Affine:
    return Affine(terms=((c % F.P, v),))


def subslice(sl: Slice, offset_elems: int, log_n: int) -> Slice:
    """A contiguous sub-range of an existing slice (offsets compose)."""
    return Slice(sl.com, sl.offset + offset_elems, log_n)


def vadd(*vs: View) -> Affine:
    return Affine(terms=tuple((1, v) for v in vs))


def vaff(terms, const=0) -> Affine:
    return Affine(terms=tuple((c % F.P, v) for c, v in terms), const=const % F.P)


# ---------------------------------------------------------------------------
# Shared context machinery.
# ---------------------------------------------------------------------------
class _Ctx:
    """State shared by prover/verifier contexts."""

    def __init__(self, transcript: Transcript, params: PCS.PCSParams):
        self.tr = transcript
        self.params = params
        self.claims: "OrderedDict[str, List[Tuple[np.ndarray, np.ndarray]]]" = OrderedDict()
        self.roots: Dict[str, np.ndarray] = {}
        self.shapes: Dict[str, Tuple[int, int]] = {}   # name -> (log_r, log_c)
        self._claim_cache: Dict[Tuple, np.ndarray] = {}
        self.lookups: List["LookupReq"] = []           # deferred LogUp work

    # -- leaf claims --------------------------------------------------------
    def _leaf_claim(self, com: str, point: jnp.ndarray) -> jnp.ndarray:
        key = (com, np.asarray(point).tobytes())
        if key in self._claim_cache:
            return jnp.asarray(self._claim_cache[key])
        value = self._leaf_claim_impl(com, point)
        _notify("on_leaf_claim", ctx=self, com=com,
                point=np.asarray(point), value=np.asarray(value))
        self.tr.absorb(value)
        self.claims.setdefault(com, []).append(
            (np.asarray(point), np.asarray(value)))
        self._claim_cache[key] = np.asarray(value)
        return value

    def _prefix_point(self, sl: Slice, point: jnp.ndarray) -> jnp.ndarray:
        """Full-commitment point for a slice claim: const prefix ++ point."""
        log_total = sum(self.shapes[sl.com])
        npfx = log_total - sl.log_n
        if not npfx:
            return point
        pfx = _const_bits_point(sl.offset >> sl.log_n, npfx)
        return jnp.concatenate([jnp.asarray(pfx), jnp.asarray(point)])

    # -- view claims ---------------------------------------------------------
    def claim(self, v: View, point: jnp.ndarray) -> jnp.ndarray:
        """MLE evaluation claim of a view at `point`, decomposed to leaves."""
        if isinstance(v, Slice):
            _notify("on_slice_claim", ctx=self, com=v.com,
                    offset=v.offset, log_n=v.log_n)
            return self._leaf_claim(v.com, self._prefix_point(v, point))
        if isinstance(v, Affine):
            acc = _fc(v.const)
            for c, sub in v.terms:
                sval = self.claim(sub, point)
                acc = F.f4add(acc, F.f4mul(_fc(c), sval))
            return acc
        if isinstance(v, BcastCols):
            base_n = view_log_n(v.base)
            return self.claim(v.base, point[:base_n])
        if isinstance(v, BcastRows):
            return self.claim(v.base, point[v.extra:])
        if isinstance(v, Concat):
            b = len(v.parts).bit_length() - 1
            eq = eq_points(point[:b])            # (2^b, 4)
            acc = F.f4zero(())
            for i, part in enumerate(v.parts):
                sub = self.claim(part, point[b:])
                acc = F.f4add(acc, F.f4mul(eq[i], sub))
            return acc
        if isinstance(v, Public):
            vec = F.f_from_int(np.array(v.values, dtype=np.int64))
            return mle_eval_base(vec, point)
        raise TypeError(v)

    def check_eq(self, a, b, what: str):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise ProofError(f"relation failed: {what}")

    def challenge_point(self, n: int) -> jnp.ndarray:
        return self.tr.challenge_f4_vec(n)


class ProverCtx(_Ctx):
    is_prover = True

    def __init__(self, transcript, params):
        super().__init__(transcript, params)
        self.tape: List = []
        self.coms: Dict[str, PCS.Commitment] = {}
        self.ints: Dict[str, np.ndarray] = {}     # committed int values

    # -- commitments ---------------------------------------------------------
    def commit(self, name: str, values: np.ndarray):
        """Commit an integer vector (padded to 2^m) under `name`."""
        n = len(values)
        total = 1 << max((n - 1).bit_length(), 0) if n > 1 else 1
        vals = np.zeros(total, dtype=np.int64)
        vals[:n] = values
        com = PCS.commit(F.f_from_int(vals), self.params)
        self.coms[name] = com
        self.ints[name] = vals
        self.roots[name] = com.root
        self.shapes[name] = (com.log_r, com.log_c)
        self.tape.append(("root", name, com.root))
        _notify("on_commit", ctx=self, name=name, root=np.asarray(com.root),
                log_total=com.log_r + com.log_c, kind="int")
        self.tr.absorb(jnp.asarray(com.root))

    def commit_field(self, name: str, fvec: jnp.ndarray, aspect: int = 0):
        """Commit a field-valued vector (already Montgomery uint32)."""
        com = PCS.commit(jnp.asarray(fvec), self.params, aspect)
        self.coms[name] = com
        self.roots[name] = com.root
        self.shapes[name] = (com.log_r, com.log_c)
        self.tape.append(("root", name, com.root))
        _notify("on_commit", ctx=self, name=name, root=np.asarray(com.root),
                log_total=com.log_r + com.log_c, kind="field")
        self.tr.absorb(jnp.asarray(com.root))

    def attach(self, name: str, com: PCS.Commitment, ints: np.ndarray):
        """Attach an externally-created commitment (boundary/weights)."""
        self.coms[name] = com
        self.ints[name] = ints
        self.roots[name] = com.root
        self.shapes[name] = (com.log_r, com.log_c)
        _notify("on_commit", ctx=self, name=name, root=np.asarray(com.root),
                log_total=com.log_r + com.log_c, kind="attach")
        self.tr.absorb(jnp.asarray(com.root))

    def _leaf_claim_impl(self, com: str, point: jnp.ndarray) -> jnp.ndarray:
        # sliced evaluation: a const-prefixed (slice) point only pays for
        # its slice — bit-identical value, see pcs.eval_at_sliced
        val = PCS.eval_at_sliced(self.coms[com], np.asarray(point))
        self.tape.append(("val", np.asarray(val)))
        return val

    # -- materialization (field vectors for sum-check factors) --------------
    def materialize(self, v: View) -> jnp.ndarray:
        if isinstance(v, Slice):
            flat = self.ints[v.com][v.offset:v.offset + (1 << v.log_n)]
            return F.f_from_int(flat)
        if isinstance(v, Affine):
            n = 1 << view_log_n(v)
            acc = jnp.broadcast_to(F.fconst(v.const), (n,))
            for c, sub in v.terms:
                acc = F.fadd(acc, F.fmul(F.fconst(c, (n,)),
                                         self.materialize(sub)))
            return acc
        if isinstance(v, BcastCols):
            base = self.materialize(v.base)
            return jnp.repeat(base, 1 << v.extra)
        if isinstance(v, BcastRows):
            base = self.materialize(v.base)
            return jnp.tile(base, 1 << v.extra)
        if isinstance(v, Concat):
            return jnp.concatenate([self.materialize(p) for p in v.parts])
        if isinstance(v, Public):
            return F.f_from_int(np.array(v.values, dtype=np.int64))
        raise TypeError(v)

    def put(self, obj):
        self.tape.append(("obj", obj))
        _notify("on_tape", ctx=self, kind="obj", payload=obj)

    def put_value(self, val: jnp.ndarray) -> jnp.ndarray:
        self.tape.append(("val", np.asarray(val)))
        _notify("on_tape", ctx=self, kind="val", payload=np.asarray(val))
        self.tr.absorb(val)
        return val

    def finalize(self) -> List:
        """Batch-open every commitment at its accumulated claim points."""
        assert not self.lookups, "finalize with pending lookups — call flush_lookups first"
        for name in self.claims:
            points = [jnp.asarray(p) for p, _ in self.claims[name]]
            values = [v for _, v in self.claims[name]]
            bundle = PCS.prove_openings(self.coms[name], points, self.tr,
                                        self.params, values=values)
            self.tape.append(("open", name, bundle))
            _notify("on_open", ctx=self, name=name, n_points=len(points))
        _notify("on_finalize", ctx=self)
        return self.tape


class VerifierCtx(_Ctx):
    is_prover = False

    def __init__(self, transcript, params, tape: List,
                 store: Optional[PCS.ColumnStore] = None):
        super().__init__(transcript, params)
        self.tape = tape
        self.cursor = 0
        self.store = store

    def _next(self, kind: str):
        if self.cursor >= len(self.tape):
            raise ProofError("proof tape exhausted")
        item = self.tape[self.cursor]
        self.cursor += 1
        if item[0] != kind:
            raise ProofError(f"tape mismatch: want {kind}, got {item[0]}")
        return item

    def commit(self, name: str, n_elems: int):
        _, got_name, root = self._next("root")
        if got_name != name:
            raise ProofError(f"commitment order mismatch: {got_name}!={name}")
        total = 1 << max((n_elems - 1).bit_length(), 0) if n_elems > 1 else 1
        self.roots[name] = root
        self.shapes[name] = PCS.shape_for(total)
        self.tr.absorb(jnp.asarray(root))

    def commit_field(self, name: str, n_elems: int, aspect: int = 0):
        _, got_name, root = self._next("root")
        if got_name != name:
            raise ProofError(f"commitment order mismatch: {got_name}!={name}")
        total = 1 << max((n_elems - 1).bit_length(), 0) if n_elems > 1 else 1
        self.roots[name] = root
        self.shapes[name] = PCS.shape_for(total, aspect)
        self.tr.absorb(jnp.asarray(root))

    def attach(self, name: str, root: np.ndarray, n_elems: int):
        total = 1 << max((n_elems - 1).bit_length(), 0) if n_elems > 1 else 1
        self.roots[name] = root
        self.shapes[name] = PCS.shape_for(total)
        self.tr.absorb(jnp.asarray(root))

    def _leaf_claim_impl(self, com: str, point: jnp.ndarray) -> jnp.ndarray:
        _, val = self._next("val")
        return jnp.asarray(val)

    def get(self):
        _, obj = self._next("obj")
        return obj

    def get_value(self) -> jnp.ndarray:
        _, val = self._next("val")
        v = jnp.asarray(val)
        self.tr.absorb(v)
        return v

    def finalize(self):
        if self.lookups:
            raise ProofError("finalize with pending lookups")
        for name in self.claims:
            _, got_name, bundle = self._next("open")
            if got_name != name:
                raise ProofError(f"opening order mismatch: {got_name}")
            points = [jnp.asarray(p) for p, _ in self.claims[name]]
            values = [jnp.asarray(v) for _, v in self.claims[name]]
            ok = PCS.verify_openings(self.roots[name], *self.shapes[name],
                                     points, values, bundle, self.tr,
                                     self.params, store=self.store)
            if not ok:
                raise ProofError(f"PCS opening failed for {name}")
        if self.cursor != len(self.tape):
            raise ProofError("unconsumed proof material")


Ctx = Union[ProverCtx, VerifierCtx]


# ---------------------------------------------------------------------------
# Gadgets. Each runs identically on both sides; prover writes tape values.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _half_point(m: int) -> np.ndarray:
    """The point (1/2, ..., 1/2) in Fp4 — see g_sum. Cached per arity."""
    out = np.zeros((m, 4), np.uint32)
    out[:, 0] = INV2 * F._R % F.P
    out.setflags(write=False)
    return out


def g_sum(ctx: Ctx, v: View) -> jnp.ndarray:
    """Returns S = sum_z v(z) via the half-point identity — no sum-check.

    For multilinear f~, eq(z, (1/2,...,1/2)) = 2^-m for EVERY z, so
    sum_z f(z) = 2^m * f~(1/2,...,1/2): a single evaluation claim replaces
    the whole single-factor sum-check (exact, not probabilistic).
    """
    m = view_log_n(v)
    return F.f4mul(_fc((1 << m) % F.P), ctx.claim(v, _half_point(m)))


def g_dot_eq(ctx: Ctx, views: Sequence[View], r: jnp.ndarray,
             total_bits: Optional[int] = None, eq_pos: str = "lead"
             ) -> jnp.ndarray:
    """Returns T with proof that T = sum_z EQ(z) * prod_i v_i(z).

    EQ covers len(r) of the index bits: leading bits ('lead', EQ broadcasts
    over trailing/column bits — a per-row reduction) or trailing bits
    ('trail', per-column reduction). With total_bits == len(r) this is the
    plain eq-weighted zerocheck kernel.
    """
    nr = r.shape[0]
    total_bits = nr if total_bits is None else total_bits
    extra = total_bits - nr
    if ctx.is_prover:
        eq = eq_points(r)
        if extra:
            if eq_pos == "lead":
                eq = jnp.repeat(eq, 1 << extra, axis=0)
            else:
                eq = jnp.tile(eq, (1 << extra, 1))
        mats = [F.f4_from_base(ctx.materialize(v)) for v in views]
        prod = eq
        for m in mats:
            prod = F.f4mul(prod, m)
        t = ctx.put_value(fsum(prod, axis=0))
        proof, rho = SC.prove([eq] + mats, ctx.tr)
        ctx.put(proof)
        finals = jnp.asarray(proof.final_evals)
    else:
        t = ctx.get_value()
        proof = ctx.get()
        ok, rho, finals = SC.verify(t, proof, 1 + len(views), ctx.tr)
        if not ok:
            raise ProofError("g_dot_eq sumcheck failed")
    rho_eq = rho[:nr] if eq_pos == "lead" else rho[extra:]
    ctx.check_eq(eq_eval(r, rho_eq), finals[0], "g_dot_eq eq factor")
    for i, v in enumerate(views):
        ctx.check_eq(ctx.claim(v, rho), finals[i + 1],
                     f"g_dot_eq factor {i}")
    return t


def g_matmul_term(ctx: Ctx, A: View, B: View, shape: Tuple[int, int, int],
                  r_i: jnp.ndarray, r_j: jnp.ndarray,
                  a_t: bool = False, b_t: bool = False) -> jnp.ndarray:
    """Returns (op(A)@op(B))~(r_i, r_j) with a Thaler sum-check over k.

    a_t/b_t: the view stores the TRANSPOSE of the operand (its natural
    witness layout); claims swap the point halves accordingly — transposes
    are free in MLE land.
    """
    n, k, m = shape
    ln, lk, lm = (x.bit_length() - 1 for x in (n, k, m))
    assert (1 << ln, 1 << lk, 1 << lm) == (n, k, m)
    if ctx.is_prover:
        Am = ctx.materialize(A).reshape((k, n) if a_t else (n, k))
        Bm = ctx.materialize(B).reshape((m, k) if b_t else (k, m))
        A_r = partial_eval_cols(Am, r_i) if a_t else partial_eval_rows(Am, r_i)
        B_c = partial_eval_rows(Bm, r_j) if b_t else partial_eval_cols(Bm, r_j)
        t = ctx.put_value(fsum(F.f4mul(A_r, B_c), axis=0))
        proof, rho = SC.prove([A_r, B_c], ctx.tr)
        ctx.put(proof)
        finals = jnp.asarray(proof.final_evals)
    else:
        t = ctx.get_value()
        proof = ctx.get()
        ok, rho, finals = SC.verify(t, proof, 2, ctx.tr)
        if not ok:
            raise ProofError("g_matmul_term sumcheck failed")
        if rho.shape[0] != lk:
            raise ProofError("g_matmul_term wrong k vars")
    a_pt = jnp.concatenate([rho, r_i]) if a_t else jnp.concatenate([r_i, rho])
    b_pt = jnp.concatenate([r_j, rho]) if b_t else jnp.concatenate([rho, r_j])
    ctx.check_eq(ctx.claim(A, a_pt), finals[0], "matmul A eval")
    ctx.check_eq(ctx.claim(B, b_pt), finals[1], "matmul B eval")
    return t


def g_rowsum(ctx: Ctx, X: View, shape: Tuple[int, int],
             r_i: jnp.ndarray) -> jnp.ndarray:
    """Returns sum_k X~(r_i, k) — half-point identity over the column vars."""
    n, k = shape
    lk = k.bit_length() - 1
    pt = jnp.concatenate([jnp.asarray(r_i), _half_point(lk)])
    return F.f4mul(_fc(k % F.P), ctx.claim(X, pt))


def g_colsum(ctx: Ctx, X: View, shape: Tuple[int, int],
             r_j: jnp.ndarray) -> jnp.ndarray:
    """Returns sum_i X~(i, r_j) — half-point identity over the row vars."""
    n, k = shape
    ln = n.bit_length() - 1
    pt = jnp.concatenate([_half_point(ln), jnp.asarray(r_j)])
    return F.f4mul(_fc(n % F.P), ctx.claim(X, pt))


@functools.lru_cache(maxsize=4096)
def _fc_cached(c: int) -> np.ndarray:
    out = np.zeros(4, np.uint32)
    out[0] = c * F._R % F.P
    out.setflags(write=False)
    return out


def _fc(c: int):
    """Fp4 constant for Python int c (numpy, Montgomery — cached: the
    gadget glue asks for the same small constants thousands of times per
    layer, and an eager jnp materialization costs ~0.3 ms each)."""
    return _fc_cached(c % F.P)


def f4_lincomb(pairs, const: int = 0) -> jnp.ndarray:
    """sum_i c_i * val_i + const over Fp4 (c_i python ints)."""
    acc = _fc(const)
    for c, val in pairs:
        acc = F.f4add(acc, F.f4mul(_fc(c), jnp.asarray(val)))
    return acc


def g_lin_relation(ctx: Ctx, views_coefs, const: int, what: str,
                   r: Optional[jnp.ndarray] = None, log_n: Optional[int] = None):
    """Check sum_i c_i * v_i + const == 0 entrywise, via a random point."""
    if r is None:
        r = ctx.challenge_point(log_n)
    acc = _fc(const)
    for c, v in views_coefs:
        acc = F.f4add(acc, F.f4mul(_fc(c % F.P), ctx.claim(v, r)))
    ctx.check_eq(acc, F.f4zero(()), what)
    return r


def g_hadamard(ctx: Ctx, a: View, b: View, c: View, what: str = "hadamard"):
    """Check c = a * b entrywise (no rounding)."""
    log_n = view_log_n(a)
    r = ctx.challenge_point(log_n)
    t = g_dot_eq(ctx, [a, b], r)
    ctx.check_eq(ctx.claim(c, r), t, what)


def g_abs(ctx: Ctx, z: View, a: View, what: str = "abs"):
    """Check a = |z| given a is separately range-bounded >= 0: a^2 == z^2."""
    log_n = view_log_n(z)
    r = ctx.challenge_point(log_n)
    t_a = g_dot_eq(ctx, [a, a], r)
    t_z = g_dot_eq(ctx, [z, z], r)
    ctx.check_eq(t_a, t_z, what)


def g_int_matmul(ctx: Ctx, A_hi: View, A_lo: View, B_hi: View, B_lo: View,
                 shape: Tuple[int, int, int],
                 a_t: bool = False, b_t: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Accumulator MLE for C = A @ B with A = 256*(A_hi-128)+(A_lo-128)+128.

    A_hi/A_lo etc. are the RAW [0,256) limb slices; centering by 128 keeps
    every limb product in [-2^14, 2^14], so accumulators stay < p/2 for
    k <= 61439 (asserted). a_t/b_t: views hold the operand transposed.
    Returns (acc~(r_i,r_j), r_i, r_j); the caller feeds the value into a
    rescale relation at point (r_i ++ r_j).
    """
    n, k, m = shape
    # |limb product| <= 128^2, so |sum_k| <= 16384*k must stay < p/2.
    assert 16384 * k < F.P // 2, "k exceeds limb-accumulator bound"
    ln, lm = n.bit_length() - 1, m.bit_length() - 1
    r_i = ctx.challenge_point(ln)
    r_j = ctx.challenge_point(lm)
    # Centered operands as single affine views: A - 128 = 256 Ah' + Al'
    # with Ah' = A_hi - 128, Al' = A_lo - 128 (same for B). The limb
    # decomposition 256^2 HH + 256 HL + 256 LH + LL factors exactly as
    # (256 Ah' + Al') @ (256 Bh' + Bl'), so ONE two-factor sum-check at a
    # single rho replaces four — same mod-p statement, and the shared rho
    # collapses the operand evaluation claims from 16 to 4 per matmul.
    Ac = vaff([(256, A_hi), (1, A_lo)], const=-(128 * 256 + 128))
    Bc = vaff([(256, B_hi), (1, B_lo)], const=-(128 * 256 + 128))
    t_cc = g_matmul_term(ctx, Ac, Bc, shape, r_i, r_j, a_t, b_t)
    if a_t:   # row sums of A = column sums of the stored A^T
        rs = g_colsum(ctx, Ac, (k, n), r_i)
    else:
        rs = g_rowsum(ctx, Ac, (n, k), r_i)
    if b_t:   # column sums of B = row sums of the stored B^T
        cs = g_rowsum(ctx, Bc, (m, k), r_j)
    else:
        cs = g_colsum(ctx, Bc, (k, m), r_j)
    # C = (A' + 128)(B' + 128) with A' = A - 128:
    # C = A'B' + 128 rowsum(A') + 128 colsum(B') + 128^2 k.
    acc = f4_lincomb([
        (1, t_cc), (128, rs), (128, cs),
    ], const=(128 * 128 * k) % F.P)
    return acc, r_i, r_j


def g_rescale(ctx: Ctx, acc_val: jnp.ndarray, r: jnp.ndarray,
              out: View, err: View, shift: int, out_bits: int,
              what: str = "rescale"):
    """Check acc + 2^(shift-1) = 2^shift * out + err at the point r.

    `err` must be an Affine view over range-checked slices covering
    [0, 2^shift); `out` a view over range-checked slices of out_bits width.
    Soundness needs 2^shift * 2^out_bits + 2^shift < p/2 (asserted).
    """
    assert (1 << (shift + out_bits)) + (1 << shift) < F.P // 2, \
        f"rescale bound {shift}+{out_bits}"
    rc = 1 << (shift - 1)
    lhs = F.f4add(jnp.asarray(acc_val), _fc(rc))
    rhs = f4_lincomb([(1 << shift, ctx.claim(out, r)),
                      (1, ctx.claim(err, r))])
    ctx.check_eq(lhs, rhs, what)


@dataclasses.dataclass(frozen=True)
class LookupReq:
    """A deferred LogUp instance, registered by g_range8/g_lut and proved
    jointly for the whole layer by flush_lookups."""
    kind: str                           # "range8" | "lut"
    table: Optional[str]                # LUT name (pair mode)
    idx: View
    out: Optional[View]
    log_n: int
    what: str
    idx_ints: Optional[np.ndarray] = None    # prover only
    out_ints: Optional[np.ndarray] = None


def g_range8(ctx: Ctx, com_name: str, n_elems: int):
    """Value-mode LogUp: every entry of commitment `com_name` in [0,256).

    Registers the instance; the proof happens in flush_lookups."""
    log_total = sum(ctx.shapes[com_name])
    full = Slice(com_name, 0, log_total)
    ints = None
    if ctx.is_prover:
        ints = ctx.ints[com_name]
        assert ints.min() >= 0 and ints.max() < 256, \
            f"{com_name} has out-of-range entries"
    ctx.lookups.append(LookupReq(
        kind="range8", table=None, idx=full, out=None, log_n=log_total,
        what=f"range8 {com_name}", idx_ints=ints))


# ---------------------------------------------------------------------------
# Witness builder: packs named 8-bit arrays into one commitment's slices.
# ---------------------------------------------------------------------------
class WitnessBuilder:
    """Allocates 8-bit witness slices for one commitment.

    All slices are range-checked in [0, 256) by a single g_range8 instance
    on the finished commitment. Wider integers are represented as digit
    compositions (`alloc_ranged`), 16-bit signed values as (hi, lo) limb
    pairs (`alloc_limbs`); both return Affine views that reconstruct the
    value by linearity.
    """

    def __init__(self, com_name: str):
        self.com_name = com_name
        self.items: "OrderedDict[str, Tuple[int, Optional[np.ndarray]]]" = OrderedDict()
        self.ties: List[Tuple[str, str, int, int]] = []  # (w, top, scale, log_n)

    def alloc(self, name: str, n: int, values: Optional[np.ndarray] = None
              ) -> str:
        """Declare (and optionally fill) an 8-bit slice of n logical entries.

        The verifier calls with values=None — the layout is a public function
        of the layer config, so both sides build identical slice maps.
        """
        target = 1 << max((n - 1).bit_length(), 0) if n > 1 else 1
        if values is not None:
            values = np.asarray(values, dtype=np.int64).reshape(-1)
            assert len(values) == n, f"slice {name}: {len(values)} != {n}"
            if target != n:
                values = np.concatenate(
                    [values, np.zeros(target - n, np.int64)])
            assert values.min() >= 0 and values.max() < 256, \
                f"slice {name} not 8-bit: [{values.min()}, {values.max()}]"
        assert name not in self.items, f"duplicate slice {name}"
        self.items[name] = (target, values)
        return name

    def alloc_limbs(self, name: str, n: int,
                    x: Optional[np.ndarray] = None) -> "LimbPair":
        """Signed 16-bit array -> (hi, lo) slices; view = 256*hi+lo-32768."""
        hi = lo = None
        if x is not None:
            x = np.asarray(x, dtype=np.int64).reshape(-1)
            assert x.min() >= -(1 << 15) and x.max() < (1 << 15), \
                f"{name} exceeds 16-bit: [{x.min()}, {x.max()}]"
            hi = (x >> 8) + 128
            lo = x & 255
        self.alloc(name + ".hi", n, hi)
        self.alloc(name + ".lo", n, lo)
        return LimbPair(self.com_name, name)

    def alloc_ranged(self, name: str, n: int, bits: int,
                     values: Optional[np.ndarray] = None) -> "RangedValue":
        """Unsigned values in [0, 2^bits) -> exact digit decomposition."""
        if values is not None:
            values = np.asarray(values, dtype=np.int64).reshape(-1)
            assert values.min() >= 0 and values.max() < (1 << bits), \
                f"{name} exceeds {bits} bits: max {values.max()}"
        ndig = (bits + 7) // 8
        rem = bits % 8
        digit_names = []
        for i in range(ndig):
            d = (values >> (8 * i)) & 255 if values is not None else None
            digit_names.append(self.alloc(f"{name}.d{i}", n, d))
        if rem:
            scale = 1 << (8 - rem)
            w = None
            if values is not None:
                w = ((values >> (8 * (ndig - 1))) & 255) * scale
            wname = self.alloc(f"{name}.w", n, w)
            log_n = (n - 1).bit_length() if n > 1 else 0
            self.ties.append((wname, digit_names[-1], scale, log_n))
        return RangedValue(self.com_name, name, ndig)

    def pack(self) -> Tuple[Dict[str, Slice], Optional[np.ndarray], int]:
        """Pack slices (descending size). Returns (slices, values|None, n)."""
        names = list(self.items)
        order = sorted(names, key=lambda nm: -self.items[nm][0])
        offset = 0
        slices: Dict[str, Slice] = {}
        for nm in order:
            n, _ = self.items[nm]
            log_n = (n - 1).bit_length() if n > 1 else 0
            slices[nm] = Slice(self.com_name, offset, log_n)
            offset += n
        total = 1 << max((offset - 1).bit_length(), 0) if offset > 1 else 1
        have_vals = all(v is not None for _, v in self.items.values())
        packed = None
        if have_vals:
            packed = np.zeros(total, dtype=np.int64)
            for nm in order:
                n, vals = self.items[nm]
                packed[slices[nm].offset:slices[nm].offset + n] = vals
        self.slices = slices
        return slices, packed, total

    def build(self, ctx) -> Dict[str, Slice]:
        """Pack, commit under this ctx, return the public slice map."""
        slices, packed, total = self.pack()
        if ctx.is_prover:
            assert packed is not None, "prover missing witness values"
            ctx.commit(self.com_name, packed)
        else:
            ctx.commit(self.com_name, total)
        _notify("on_witness_slices", ctx=ctx, com=self.com_name,
                slices=dict(slices))
        return slices

    def run_checks(self, ctx, slices: Dict[str, Slice]):
        """Range-check the whole commitment + digit-tie relations."""
        n_elems = 1 << sum(ctx.shapes[self.com_name])
        g_range8(ctx, self.com_name, n_elems)
        for wname, topname, scale, _ in self.ties:
            w_sl, top_sl = slices[wname], slices[topname]
            g_lin_relation(ctx, [(1, w_sl), (-scale, top_sl)], 0,
                           f"digit tie {wname}", log_n=w_sl.log_n)


@dataclasses.dataclass(frozen=True)
class LimbPair:
    com: str
    name: str

    def view(self, slices: Dict[str, Slice]) -> Affine:
        return vaff([(256, slices[self.name + ".hi"]),
                     (1, slices[self.name + ".lo"])], const=-32768)

    def hi(self, slices):
        return slices[self.name + ".hi"]

    def lo(self, slices):
        return slices[self.name + ".lo"]


@dataclasses.dataclass(frozen=True)
class RangedValue:
    com: str
    name: str
    ndig: int

    def view(self, slices: Dict[str, Slice]) -> Affine:
        return vaff([(1 << (8 * i), slices[f"{self.name}.d{i}"])
                     for i in range(self.ndig)])


def g_lut(ctx: Ctx, table_name: str, idx: View, out: View,
          idx_ints: Optional[np.ndarray], out_ints: Optional[np.ndarray],
          n_elems: int, what: str = "lut"):
    """Pair-mode LogUp: (idx_i, out_i) in {(j, T[j])} for a standard LUT.

    idx/out views must cover n_elems padded to 2^m with valid pairs —
    callers pad idx with 0 and out with T[0].  Registers the instance; the
    proof happens in flush_lookups.
    """
    log_n = view_log_n(idx)
    assert view_log_n(out) == log_n, "lut idx/out view size mismatch"
    if ctx.is_prover:
        idx_ints = np.asarray(idx_ints, dtype=np.int64).reshape(-1)
        out_ints = np.asarray(out_ints, dtype=np.int64).reshape(-1)
        assert len(idx_ints) == (1 << log_n) == len(out_ints), \
            f"lut {what}: ints not padded to view size"
    ctx.lookups.append(LookupReq(
        kind="lut", table=table_name, idx=idx, out=out, log_n=log_n,
        what=what, idx_ints=idx_ints, out_ints=out_ints))


def _lookup_w_f4(ctx: "ProverCtx", req: LookupReq,
                 beta: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Prover-side witness fingerprint vector w for one instance."""
    if req.kind == "range8":
        return F.f4_from_base(F.f_from_int(req.idx_ints))
    return LK.combine_pair(F.f_from_int(req.idx_ints),
                           F.f_from_int(req.out_ints), beta)


def _lookup_table_sum(req: LookupReq, counts_info, beta, alpha
                      ) -> jnp.ndarray:
    """Verifier-computable S_b = sum_j m_j/(alpha - t_j)."""
    if req.kind == "range8":
        counts = counts_info
        support = np.arange(256, dtype=np.int64)
        t_vals = F.f4_from_base(F.f_from_int(support))
        return LK.table_inverse_sum(t_vals, counts, alpha)
    support, counts = counts_info
    # The support's length varies with the data: zero-count padding to a
    # power of two adds exact zeros to the sum and keeps the programs below
    # to one shape per bucket instead of one per query.
    pad = (1 << max(len(support) - 1, 0).bit_length()) - len(support)
    support, counts = np.pad(support, (0, pad)), np.pad(counts, (0, pad))
    table = LUTS.table_q(req.table).astype(np.int64)
    t_vals = LK.combine_pair(F.f_from_int(support),
                             F.f_from_int(table[support]), beta)
    return LK.table_inverse_sum(t_vals, counts, alpha)


def flush_lookups(ctx: Ctx, helper_name: str = "lkh", aspect: int = 0):
    """Prove/verify every registered LogUp instance for this context.

    One shared base-field helper commitment holds the inverse columns
    a = 1/(alpha - w) of ALL instances (4 Fp4 coefficient planes each) as
    aligned slices; multiplicities travel in the clear (see lookup.py).
    Per instance: a half-point sum claim ties S_a to the verifier-computed
    table sum, and one degree-3 zerocheck ties a to the witness views.
    All evaluation claims join the layer's batched PCS openings.
    """
    reqs, ctx.lookups = ctx.lookups, []
    if not reqs:
        return
    # 1. per-instance beta + multiplicities (absorbed before alpha)
    betas: List[Optional[jnp.ndarray]] = []
    infos: List = []
    for req in reqs:
        beta = ctx.tr.challenge_f4() if req.kind == "lut" else None
        betas.append(beta)
        n_i = 1 << req.log_n
        if req.kind == "range8":
            if ctx.is_prover:
                counts = LK.dense_counts(req.idx_ints, 256)
                # counts < 2^31, so ship uint32: the codec 31-bit packs it
                ctx.put(("m", counts.astype(np.uint32)))
            else:
                obj = ctx.get()
                if not (isinstance(obj, tuple) and len(obj) == 2
                        and obj[0] == "m"):
                    raise ProofError(f"{req.what}: bad multiplicity object")
                try:
                    counts = LK.check_dense_counts(obj[1], 256, n_i)
                except LK.BadMultiplicities as e:
                    raise ProofError(f"{req.what}: {e}") from e
            ctx.tr.absorb(F.f_from_int(counts))
            infos.append(counts)
        else:
            if ctx.is_prover:
                support, counts = LK.sparse_counts(req.idx_ints,
                                                   LUTS.LUT_SIZE)
                ctx.put(("msp", support.astype(np.uint32),
                         counts.astype(np.uint32)))
            else:
                obj = ctx.get()
                if not (isinstance(obj, tuple) and len(obj) == 3
                        and obj[0] == "msp"):
                    raise ProofError(f"{req.what}: bad multiplicity object")
                try:
                    support, counts = LK.check_sparse_counts(
                        obj[1], obj[2], LUTS.LUT_SIZE, n_i)
                except LK.BadMultiplicities as e:
                    raise ProofError(f"{req.what}: {e}") from e
            ctx.tr.absorb(F.f_from_int(support))
            ctx.tr.absorb(F.f_from_int(counts))
            infos.append((support, counts))
    # 2. shared alpha, drawn after every witness root and multiplicity
    alpha = ctx.tr.challenge_f4()
    # 3. helper commitment layout: 4 coefficient planes per instance,
    #    packed descending by size (public function of the layer config)
    items = [(i, k, 1 << reqs[i].log_n)
             for i in range(len(reqs)) for k in range(4)]
    order = sorted(range(len(items)), key=lambda t: -items[t][2])
    offsets: Dict[Tuple[int, int], int] = {}
    off = 0
    for t in order:
        i, k, sz = items[t]
        offsets[(i, k)] = off
        off += sz
    total = 1 << max((off - 1).bit_length(), 0) if off > 1 else 1
    a_vecs: List[Optional[jnp.ndarray]] = [None] * len(reqs)
    if ctx.is_prover:
        helper = np.zeros(total, dtype=np.uint32)
        inv_ahead = {}
        if KOPS.on_tpu():   # one inversion program per instance length
            inv_ahead = {r.log_n: AH.start(F.f4inv, jax.ShapeDtypeStruct(
                (1 << r.log_n, 4), jnp.uint32)) for r in reqs}
        for i, req in enumerate(reqs):
            w = _lookup_w_f4(ctx, req, betas[i])
            ab = jnp.broadcast_to(alpha, w.shape)
            if inv_ahead:
                AH.wait(inv_ahead[req.log_n])
            a = F.f4inv(F.f4sub(ab, w))                  # (n_i, 4)
            a_vecs[i] = a
            a_np = np.asarray(a)
            n_i = 1 << req.log_n
            for k in range(4):
                helper[offsets[(i, k)]:offsets[(i, k)] + n_i] = a_np[:, k]
        ctx.commit_field(helper_name, jnp.asarray(helper), aspect)
    else:
        ctx.commit_field(helper_name, total, aspect)
    # 4. per-instance sum tie + zerocheck
    for i, req in enumerate(reqs):
        coeffs = [Slice(helper_name, offsets[(i, k)], req.log_n)
                  for k in range(4)]
        hp = _half_point(req.log_n)
        a_half = PCS.combine_f4_values([ctx.claim(s, hp) for s in coeffs])
        s_a = F.f4mul(_fc((1 << req.log_n) % F.P), a_half)
        s_b = _lookup_table_sum(req, infos[i], betas[i], alpha)
        ctx.check_eq(s_a, s_b, f"{req.what} logup sum")
        r = ctx.challenge_point(req.log_n)
        if ctx.is_prover:
            eq_r = eq_points(r)
            w = _lookup_w_f4(ctx, req, betas[i])
            ab = jnp.broadcast_to(alpha, w.shape)
            proof, rho = SC.prove([eq_r, a_vecs[i], F.f4sub(ab, w)], ctx.tr)
            ctx.put(proof)
            finals = jnp.asarray(proof.final_evals)
        else:
            proof = ctx.get()
            ok, rho, finals = SC.verify(_fc(1), proof, 3, ctx.tr)
            if not ok:
                raise ProofError(f"{req.what} zerocheck failed")
            if rho.shape[0] != req.log_n:
                raise ProofError(f"{req.what} zerocheck wrong arity")
        ctx.check_eq(eq_eval(r, rho), finals[0], f"{req.what} eq factor")
        a_rho = PCS.combine_f4_values([ctx.claim(s, rho) for s in coeffs])
        ctx.check_eq(a_rho, finals[1], f"{req.what} inverse column")
        # The range8 witness tie claims the FULL commitment; tag it so
        # tape_lint does not count it as constraining individual slices
        # (a slice with ONLY this claim is range-checked but otherwise
        # unconstrained — exactly the bug class the lint must flag).
        if req.kind == "range8":
            _notify("on_range_tie", ctx=ctx, com=req.idx.com)
        w_rho = ctx.claim(req.idx, rho)
        if req.kind == "lut":
            w_rho = F.f4add(w_rho, F.f4mul(betas[i], ctx.claim(req.out, rho)))
        ctx.check_eq(F.f4sub(alpha, w_rho), finals[2],
                     f"{req.what} witness tie")


# ---------------------------------------------------------------------------
# Head-batched matmuls: the same product over 2^lh heads stored one after
# another, proved as one argument.
# ---------------------------------------------------------------------------
@jax.jit
def _bind_mid(mat: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Base (H, R, C) with the R variables bound to r -> Fp4 (H*C, 4)."""
    per_head = jax.vmap(partial_eval_rows, in_axes=(0, None))
    return per_head(mat, r).reshape(-1, 4)


def g_matmul_term_heads(ctx: Ctx, A: View, B: View,
                        shape: Tuple[int, int, int], r_h: jnp.ndarray,
                        r_i: jnp.ndarray, r_j: jnp.ndarray,
                        a_t: bool = False, b_t: bool = False) -> jnp.ndarray:
    """Returns sum_h eq(r_h, h) (op(A_h) @ op(B_h))~(r_i, r_j): one
    sum-check over (h, k) of eq(r_h, h) * A_r(h, k) * B_c(h, k), the eq
    factor leading and broadcast over k (as g_dot_eq's 'lead').

    A and B hold 2^len(r_h) heads, each laid out as g_matmul_term's
    operand.  The eq factor stays a factor of its own: the verifier
    computes eq(r_h, rho_h), and A_r~(rho_h, rho_k) is a claim on A.
    """
    n, k, m = shape
    lh, lk = r_h.shape[0], k.bit_length() - 1
    assert 1 << lk == k
    if ctx.is_prover:
        hs = 1 << lh
        Am, Bm = ctx.materialize(A), ctx.materialize(B)
        A_r = (partial_eval_cols(Am.reshape(hs * k, n), r_i) if a_t
               else _bind_mid(Am.reshape(hs, n, k), r_i))
        B_c = (_bind_mid(Bm.reshape(hs, m, k), r_j) if b_t
               else partial_eval_cols(Bm.reshape(hs * k, m), r_j))
        eq = jnp.repeat(eq_points(r_h), k, axis=0)
        t = ctx.put_value(fsum(F.f4mul(F.f4mul(eq, A_r), B_c), axis=0))
        proof, rho = SC.prove([eq, A_r, B_c], ctx.tr)
        ctx.put(proof)
        finals = jnp.asarray(proof.final_evals)
    else:
        t = ctx.get_value()
        proof = ctx.get()
        ok, rho, finals = SC.verify(t, proof, 3, ctx.tr)
        if not ok:
            raise ProofError("g_matmul_term_heads sumcheck failed")
        if rho.shape[0] != lh + lk:
            raise ProofError("g_matmul_term_heads wrong (h, k) vars")
    rho_h, rho_k = rho[:lh], rho[lh:]
    ctx.check_eq(eq_eval(r_h, rho_h), finals[0], "head matmul eq factor")
    a_pt = (jnp.concatenate([rho_h, rho_k, r_i]) if a_t
            else jnp.concatenate([rho_h, r_i, rho_k]))
    b_pt = (jnp.concatenate([rho_h, r_j, rho_k]) if b_t
            else jnp.concatenate([rho_h, rho_k, r_j]))
    ctx.check_eq(ctx.claim(A, a_pt), finals[1], "head matmul A eval")
    ctx.check_eq(ctx.claim(B, b_pt), finals[2], "head matmul B eval")
    return t


def g_int_matmul_heads(ctx: Ctx, A_hi: View, A_lo: View, B_hi: View,
                       B_lo: View, shape: Tuple[int, int, int], lh: int,
                       a_t: bool = False, b_t: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """g_int_matmul for 2^lh heads at once: returns (acc~(r_h, r_i, r_j),
    r_h ++ r_i ++ r_j), acc being every head's limb accumulator stored
    head after head.  The centring terms carry over linearly: row sums of
    A' are a claim at (r_h, r_i, 1/2...), column sums of B' at
    (r_h, 1/2..., r_j), and 128^2 k is unchanged as sum_h eq(r_h, h) = 1.
    With lh == 0 this is g_int_matmul itself, draw for draw.
    """
    if not lh:
        acc, r_i, r_j = g_int_matmul(ctx, A_hi, A_lo, B_hi, B_lo, shape,
                                     a_t=a_t, b_t=b_t)
        return acc, jnp.concatenate([r_i, r_j])
    n, k, m = shape
    assert 16384 * k < F.P // 2, "k exceeds limb-accumulator bound"
    ln, lk, lm = (x.bit_length() - 1 for x in (n, k, m))
    r_h = ctx.challenge_point(lh)
    r_i = ctx.challenge_point(ln)
    r_j = ctx.challenge_point(lm)
    Ac = vaff([(256, A_hi), (1, A_lo)], const=-(128 * 256 + 128))
    Bc = vaff([(256, B_hi), (1, B_lo)], const=-(128 * 256 + 128))
    t_cc = g_matmul_term_heads(ctx, Ac, Bc, shape, r_h, r_i, r_j, a_t, b_t)
    hk = jnp.asarray(_half_point(lk))
    a_pt = (jnp.concatenate([r_h, hk, r_i]) if a_t
            else jnp.concatenate([r_h, r_i, hk]))
    b_pt = (jnp.concatenate([r_h, r_j, hk]) if b_t
            else jnp.concatenate([r_h, hk, r_j]))
    rs = F.f4mul(_fc(k), ctx.claim(Ac, a_pt))
    cs = F.f4mul(_fc(k), ctx.claim(Bc, b_pt))
    acc = f4_lincomb([(1, t_cc), (128, rs), (128, cs)],
                     const=(128 * 128 * k) % F.P)
    return acc, jnp.concatenate([r_h, r_i, r_j])
