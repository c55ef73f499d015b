"""Ligero/Brakedown-style multilinear polynomial commitment scheme.

TPU adaptation of the paper's Halo2-IPA commitments (DESIGN.md §2): instead of
elliptic-curve MSMs we commit to a vector v of length N = 2^m by

  1. reshaping it into an R x C matrix (row-major, C = 2^ceil(m/2)),
  2. Reed-Solomon encoding every row at rate 1/blowup (NTT),
  3. Merkle-committing the C*blowup columns with Poseidon2.

An evaluation of the multilinear extension V(r) factors through the matrix:
V(r) = b^T M a with a = eq(r_cols), b = eq(r_rows). The prover reveals
u = b^T M; by row-linearity of the code, Enc(u) must agree with b^T Enc(M)
at every column, which the verifier spot-checks on `queries` random columns
(opened against the Merkle root).

Openings come in two flavours:

* k <= 1 points — the classic Ligero opening: one u row per point plus a
  dedicated random-combination proximity row.
* k >= 2 points — wire-batched: the k evaluation claims are folded with a
  transcript challenge gamma into a single sum-check over
  sum_z M~(z) * E(z),  E(z) = sum_i gamma^i eq(z, q_i),
  whose reduced point pt is transcript-random.  Only ONE u row (at pt) ships
  regardless of k, and no separate proximity row is needed: a tensor query
  at a random point doubles as the proximity test (Diamond–Posen style
  tensor-query soundness).  For the toy model this is the difference between
  233 u rows and 1.

Column openings can either carry inline Merkle paths (v1 wire) or be looked
up in a pre-verified :class:`ColumnStore` (v2 wire, one multiproof per root
per attestation) — pass ``store=`` to :func:`verify_openings`.

Soundness knobs: `security_bits(params)` reports the query-phase error
(1+rho)/2 per query — the standard Ligero distance bound — plus the field
soundness of the batching. All arithmetic is uint32 Montgomery (field.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import field as F
from . import merkle as M
from . import ntt as N
from . import sumcheck as SC
from . import transcript as T
from .mle import eq_eval, eq_points, fsum, mle_eval_base, partial_eval_rows
from .transcript import Transcript

from repro.kernels import ahead as AH
from repro.kernels import ops as KOPS


@dataclasses.dataclass(frozen=True)
class PCSParams:
    blowup: int = 4
    queries: int = 64

    def security_bits(self) -> float:
        rho = 1.0 / self.blowup
        per_query = (1.0 + rho) / 2.0
        return -self.queries * math.log2(per_query)


@dataclasses.dataclass
class Commitment:
    mat: jnp.ndarray        # (R, C) base-field message rows
    enc: jnp.ndarray        # (R, C*blowup) encoded rows
    tree: M.MerkleTree      # over columns of enc
    log_r: int
    log_c: int

    @property
    def root(self) -> np.ndarray:
        return np.asarray(self.tree.root)


@dataclasses.dataclass
class OpeningBundle:
    """PCS opening payload.

    Legacy (k <= 1 points): us has one row per point, u_prox is present,
    batch_sc is None.  Batched (k >= 2): us is the single reduced row,
    u_prox is None, batch_sc carries the claim-folding sum-check.
    columns/paths are None when the columns travel out-of-band in a
    ColumnStore (v2 wire)."""
    us: np.ndarray                       # (k or 1, C, 4)
    u_prox: Optional[np.ndarray]         # (C, 4) or None (batched)
    columns: Optional[np.ndarray]        # (t, R) or None (store mode)
    paths: Optional[List[M.MerklePath]]  # None in store mode
    batch_sc: Optional[SC.SumcheckProof] = None


class ColumnStore:
    """Per-root verified column cache for deduplicated openings.

    A v2 attestation ships, per Merkle root, ONE multiproof covering every
    queried column of every bundle that opens against that root — shared
    authentication-path prefixes are shipped once.  After the multiproof is
    checked (merkle.verify_multiproof) its columns are registered here and
    verify_openings(store=...) gathers them instead of re-verifying paths."""

    def __init__(self):
        self._cols: Dict[bytes, Dict[int, np.ndarray]] = {}

    def add_root(self, root: np.ndarray, indices: Sequence[int],
                 columns: np.ndarray) -> None:
        d = self._cols.setdefault(np.asarray(root).tobytes(), {})
        for i, col in zip(indices, np.asarray(columns)):
            d[int(i)] = col

    def has_root(self, root: np.ndarray) -> bool:
        return np.asarray(root).tobytes() in self._cols

    def gather(self, root: np.ndarray, idx: Sequence[int], n_rows: int
               ) -> Optional[jnp.ndarray]:
        d = self._cols.get(np.asarray(root).tobytes())
        if d is None:
            return None
        rows = []
        for j in idx:
            col = d.get(int(j))
            if col is None or col.shape != (n_rows,):
                return None
            rows.append(col)
        if not rows:
            return None
        return jnp.asarray(np.stack(rows).astype(np.uint32))


def shape_for(n_elems: int, aspect: int = 0) -> Tuple[int, int]:
    """Matrix shape for an n-element vector.  aspect > 0 skews toward more
    rows (R = 2^aspect * C), trading u-row bytes for column bytes."""
    m = max((n_elems - 1).bit_length(), 0) if n_elems > 1 else 0
    log_c = max(0, (m + 1) // 2 - aspect)
    log_r = m - log_c
    return log_r, log_c


def _rs_encode(rows: jnp.ndarray, blowup: int) -> jnp.ndarray:
    """RS-encode rows, routed through the NTT kernel on the fused path.

    The kernel runs the identical butterfly schedule over the identical
    twiddles, so codewords are bit-identical either way (ntt.py is the
    oracle).  Routing lives here rather than in ntt.py to keep core/ntt
    free of a kernels import cycle."""
    c = rows.shape[-1]
    n = c * blowup
    if KOPS.use_fused() and n > 1:
        padded = jnp.concatenate(
            [rows, jnp.zeros(rows.shape[:-1] + (n - c,), dtype=rows.dtype)],
            axis=-1)
        out = KOPS.ntt(padded.reshape(-1, n))
        return out.reshape(rows.shape[:-1] + (n,))
    return N.rs_encode(rows, blowup)


def commit(vec: jnp.ndarray, params: PCSParams, aspect: int = 0) -> Commitment:
    """vec: flat base-field (Montgomery uint32) array; zero-padded to 2^m."""
    n = vec.shape[0]
    log_r, log_c = shape_for(n, aspect)
    total = 1 << (log_r + log_c)
    if total != n:
        vec = jnp.concatenate([vec, jnp.zeros((total - n,), jnp.uint32)])
    mat = vec.reshape(1 << log_r, 1 << log_c)
    # the tree's programs compile while the rows encode
    M.prepare(((1 << log_c) * params.blowup, 1 << log_r))
    enc = _rs_encode(mat, params.blowup)
    tree = M.commit(enc.T)                      # leaves are columns
    return Commitment(mat=mat, enc=enc, tree=tree, log_r=log_r, log_c=log_c)


def commit_batch(vecs: Sequence[jnp.ndarray], params: PCSParams
                 ) -> List[Commitment]:
    """Commit a group of equal-length vectors through one vectorized path.

    The RS encode is a single batched NTT over a (B, R, C) stack and the
    Merkle layer is one batched sponge/compress pass (merkle.commit_batch),
    so committing all L+1 layer boundaries of a model costs one dispatch
    sequence instead of L+1.  Each returned Commitment is bit-identical to
    ``commit(vecs[i], params)``.
    """
    if not vecs:
        return []
    n = vecs[0].shape[0]
    assert all(v.shape[0] == n for v in vecs), "commit_batch needs equal lengths"
    log_r, log_c = shape_for(n)
    total = 1 << (log_r + log_c)
    mats = jnp.stack([
        (jnp.concatenate([v, jnp.zeros((total - n,), jnp.uint32)])
         if total != n else v).reshape(1 << log_r, 1 << log_c)
        for v in vecs])                                  # (B, R, C)
    M.prepare((len(vecs), (1 << log_c) * params.blowup, 1 << log_r))
    enc = _rs_encode(mats, params.blowup)                # (B, R, C*blowup)
    trees = M.commit_batch(jnp.swapaxes(enc, 1, 2))      # leaves are columns
    return [Commitment(mat=mats[i], enc=enc[i], tree=trees[i],
                       log_r=log_r, log_c=log_c) for i in range(len(vecs))]


@functools.partial(jax.jit, static_argnames=("log_r",))
def _eval_at_impl(mat: jnp.ndarray, point: jnp.ndarray, log_r: int
                  ) -> jnp.ndarray:
    u = partial_eval_rows(mat, point[:log_r])   # (C, 4)
    a = eq_points(point[log_r:])                # (C, 4)
    return fsum(F.f4mul(u, a), axis=0)


def eval_at(com: Commitment, point: jnp.ndarray) -> jnp.ndarray:
    """Prover-side MLE evaluation (4,) at point (log_r+log_c, 4).

    Global convention (mle.py): point = [row_point, col_point], MSB-first.
    """
    return _eval_at_impl(com.mat, jnp.asarray(point), com.log_r)


@functools.partial(jax.jit, static_argnames=("log_r",))
def _batched_values_impl(mat: jnp.ndarray, pts: jnp.ndarray, log_r: int
                         ) -> jnp.ndarray:
    """eval_at for all k points in one dispatch: pts (k, m, 4) -> (k, 4)."""
    return jax.vmap(lambda p: _eval_at_impl(mat, p, log_r))(pts)


@jax.jit
def _absorb_values_scan(state: jnp.ndarray, values: jnp.ndarray
                        ) -> jnp.ndarray:
    """Absorb k Fp4 values one-by-one (the batched-opening schedule) in a
    single dispatch.  Each scan step is exactly transcript.absorb(v): the
    resulting sponge state is byte-identical to the k-call loop."""
    def step(st, v):
        return T._absorb_any(st, v, 4), None
    state, _ = jax.lax.scan(step, state, values)
    return state


def _const_prefix_split(point_np: np.ndarray) -> Tuple[int, int]:
    """Longest leading run of exact 0/1 rows of a host-side point.

    Returns (s, idx): the first s rows of the point are the bits of idx
    (MSB first, exact Montgomery constants).  For such a point the MLE
    factorizes, eq(point, z) = [z_hi == idx] * eq(point[s:], z_lo), so any
    evaluation/eq-table work collapses from the full 2^m commitment onto
    the 2^(m-s) slice — and slice claims (circuit._prefix_point) are the
    overwhelming majority of PCS claims."""
    s, idx = 0, 0
    for row in np.asarray(point_np):
        if row[1] or row[2] or row[3]:
            break
        if row[0] == 0:
            bit = 0
        elif row[0] == F.R_MOD_P:
            bit = 1
        else:
            break
        idx = (idx << 1) | bit
        s += 1
    return s, idx


def eval_at_sliced(com: Commitment, point_np: np.ndarray) -> jnp.ndarray:
    """``eval_at`` that pays only for the slice a const-prefixed point
    addresses (bit-identical value: the out-of-slice eq factors are exact
    zeros, so the full sum collapses to the slice sum)."""
    point_np = np.asarray(point_np)
    s, idx = _const_prefix_split(point_np)
    m = com.log_r + com.log_c
    if s == 0 or s > m:
        return eval_at(com, jnp.asarray(point_np))
    t = m - s
    flat = com.mat.reshape(-1)
    return mle_eval_base(
        jax.lax.dynamic_slice(flat, (idx << t,), (1 << t,)),
        jnp.asarray(point_np[s:]))


@functools.partial(jax.jit, static_argnames=("k",))
def _gamma_powers(gamma: jnp.ndarray, k: int) -> jnp.ndarray:
    """(k, 4): gamma^0 .. gamma^(k-1)."""
    def step(w, _):
        return F.f4mul(w, gamma), w
    _, ws = jax.lax.scan(step, F.f4one(()), None, length=k)
    return ws


_E_CHUNK_LOG = 16      # e_vec entries written per loop step: 2^16


@functools.partial(jax.jit, static_argnames=("t",), donate_argnums=(0,))
def _bucket_e_impl(e: jnp.ndarray, sufs: jnp.ndarray, ws_ext: jnp.ndarray,
                   widx: jnp.ndarray, los: jnp.ndarray, t: int) -> jnp.ndarray:
    """Add one suffix-length bucket of claim groups into e (n_tot, 4),
    in place.  sufs: (G, Mx, t, 4) group-member suffixes (zero-padded
    slots), ws_ext: (k+1, 4) gamma powers with a trailing zero row, widx:
    (G, Mx) per-slot claim index (padding slots point at the zero row, so
    they contribute exactly nothing), los: (G,) slice offsets.  Groups
    within a bucket share t but have distinct prefixes, so their slices
    are disjoint.

    A group's 2^t slice is written in chunks of 2^c: eq(suf, x_hi x_lo) =
    eq(suf[:h], x_hi) * eq(suf[h:], x_lo), so a chunk is one Fp4 scalar
    per member times that member's 2^c-long low table.  No 2^t table is
    ever built: at t = 25 one took 11 GiB of temporaries on a TPU v5e."""
    c = min(t, _E_CHUNK_LOG)
    h = t - c
    ws = ws_ext[widx]                                         # (G, Mx, 4)
    tables = jax.vmap(jax.vmap(eq_points))
    lo = tables(sufs[:, :, h:])                               # (G, Mx, 2^c, 4)
    whi = F.f4mul(ws[:, :, None, :], tables(sufs[:, :, :h]))  # (G, Mx, 2^h, 4)

    def put(i, e):
        g, j = i >> h, i & ((1 << h) - 1)
        chunk = fsum(F.f4mul(whi[g, :, j][:, None, :], lo[g]), axis=0)
        at = (los[g] + (j << c), 0)
        cur = jax.lax.dynamic_slice(e, at, (1 << c, 4))
        return jax.lax.dynamic_update_slice(e, F.f4add(cur, chunk), at)

    return jax.lax.fori_loop(0, sufs.shape[0] << h, put, e)


def _build_e_vec(n_tot: int, pts_np: Sequence[np.ndarray],
                 gamma: jnp.ndarray) -> jnp.ndarray:
    """e_vec = sum_i gamma^i eq(pts[i], .) built slice-wise.

    Claims are grouped by the slice their const-bit prefix addresses, then
    groups are bucketed by suffix length t: each bucket is ONE jitted
    dispatch that adds into e_vec in place (distinct prefixes within a
    bucket address disjoint slices).  Values are identical to the naive
    sequential fold (exact mod-p arithmetic is reduction-order-free and
    zero-weight padding slots are exact additive identities), but the work
    drops from k*N to the sum of the touched slice sizes, in a handful of
    dispatches."""
    m = n_tot.bit_length() - 1
    k = len(pts_np)
    e_vec = jnp.zeros((n_tot, 4), jnp.uint32)
    if k == 0:
        return e_vec
    ws_ext = jnp.concatenate(
        [_gamma_powers(gamma, k), jnp.zeros((1, 4), jnp.uint32)])
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, p in enumerate(pts_np):
        s, idx = _const_prefix_split(p)
        if s > m:                       # fully-constant point: keep 0 vars
            idx >>= s - m
            s = m
        groups.setdefault((m - s, idx), []).append(i)
    buckets: Dict[int, List[Tuple[int, List[int]]]] = {}
    for (t, idx), members in groups.items():
        buckets.setdefault(t, []).append((idx, members))
    # Group, member and weight counts are padded to powers of two, so
    # commitments and queries with similar claim sets share one program
    # per suffix length; a padding group has only zero-weight slots and
    # adds exact zeros (into slice 0).
    ws_ext = jnp.concatenate([ws_ext, jnp.zeros(
        (_pow2(k + 1) - k - 1, 4), jnp.uint32)])
    args = []
    for t in sorted(buckets):
        glist = sorted(buckets[t])
        G = _pow2(len(glist))
        mx = _pow2(max(len(mem) for _, mem in glist))
        sufs = np.zeros((G, mx, t, 4), np.uint32)
        widx = np.full((G, mx), k, np.int32)   # padding -> zero weight row
        los = np.zeros((G,), np.int32)
        for g, (idx, members) in enumerate(glist):
            los[g] = idx << t
            for j, i in enumerate(members):
                sufs[g, j] = pts_np[i][m - t:]
                widx[g, j] = i
        args.append((sufs, widx, los, t))
    ahead = []
    if KOPS.on_tpu():                  # every bucket compiles in parallel
        ahead = [AH.start(_bucket_e_impl, *(jax.ShapeDtypeStruct(
            x.shape, x.dtype) for x in (e_vec, sufs, ws_ext, widx, los)), t)
            for sufs, widx, los, t in args]
    for b, (sufs, widx, los, t) in enumerate(args):
        if ahead:
            AH.wait(ahead[b])
        e_vec = _bucket_e_impl(e_vec, jnp.asarray(sufs), ws_ext,
                               jnp.asarray(widx), jnp.asarray(los), t)
    return e_vec


def _pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def _encode_f4_row(u: jnp.ndarray, blowup: int) -> jnp.ndarray:
    """RS-encode an Fp4 row (C,4) coefficient-wise -> (C*blowup, 4)."""
    return N.rs_encode(u.T, blowup).T


def _gamma_fold(values: Sequence[jnp.ndarray], gamma: jnp.ndarray
                ) -> jnp.ndarray:
    """sum_i gamma^i * values[i], values (4,) each."""
    acc = jnp.zeros((4,), jnp.uint32)
    w = F.f4one(())
    for v in values:
        acc = F.f4add(acc, F.f4mul(w, jnp.asarray(v)))
        w = F.f4mul(w, gamma)
    return acc


def prove_openings(com: Commitment, points: Sequence[jnp.ndarray],
                   transcript: Transcript, params: PCSParams,
                   values: Optional[Sequence[np.ndarray]] = None
                   ) -> OpeningBundle:
    """Open the commitment at each point (batched when >= 2 points).

    ``values`` optionally carries the already-computed claim values (the
    circuit layer knows them — it absorbed each at claim time); when given,
    the batched path skips re-evaluating the MLE at every point."""
    points = [jnp.asarray(p) for p in points]
    if len(points) >= 2:
        return _prove_openings_batched(com, points, transcript, params,
                                       values)
    us = []
    for point in points:
        r_rows = point[:com.log_r]
        u = partial_eval_rows(com.mat, r_rows)
        transcript.absorb(u)
        us.append(np.asarray(u))
    rho = transcript.challenge_f4_vec(com.mat.shape[0])      # (R, 4)
    # u_prox[c] = sum_r rho[r] * mat[r, c]  (Fp4 x base, coefficient-wise)
    u_prox = fsum(F.fmul(rho[:, None, :], com.mat[:, :, None]), axis=0)
    transcript.absorb(u_prox)
    n_cols = com.enc.shape[1]
    idx = transcript.challenge_indices(n_cols, params.queries)
    columns = np.asarray(com.enc[:, idx]).T                  # (t, R)
    paths = M.batch_open(com.tree, idx)
    return OpeningBundle(us=np.stack(us) if us else np.zeros((0,) + (com.mat.shape[1], 4), np.uint32),
                         u_prox=np.asarray(u_prox), columns=columns, paths=paths)


def _prove_openings_batched(com: Commitment, points: Sequence[jnp.ndarray],
                            transcript: Transcript, params: PCSParams,
                            values: Optional[Sequence[np.ndarray]] = None
                            ) -> OpeningBundle:
    """gamma-fold all claims into one sum-check, open once at its point.

    The k-claim prologue (k MLE evaluations, k value absorbs, the e_vec
    build) ran as O(k) eager op chains over the FULL commitment and
    dominated layer proving (54% of prove_layer).  Now: values arrive
    precomputed (or one vmapped dispatch), the k absorbs are one scanned
    dispatch, and e_vec is built slice-wise (_build_e_vec).  All values and
    sponge states are bit-identical to the naive loop (exact arithmetic)."""
    pts_np = [np.asarray(p) for p in points]
    if values is None:
        pts = jnp.stack([jnp.asarray(p) for p in points])    # (k, m, 4)
        vals = _batched_values_impl(com.mat, pts, com.log_r)  # (k, 4)
    else:
        assert len(values) == len(points)
        vals = jnp.asarray(np.stack([np.asarray(v) for v in values]))
    transcript.set_state(_absorb_values_scan(transcript.state, vals))
    gamma = transcript.challenge_f4()
    m_lift = F.f4_from_base(com.mat.reshape(-1))             # (N, 4)
    e_vec = _build_e_vec(com.mat.size, pts_np, gamma)
    sc, pt = SC.prove([m_lift, e_vec], transcript)
    if KOPS.use_fused():
        u = KOPS.partial_eval_rows_mm(com.mat, pt[:com.log_r])  # (C, 4)
    else:
        u = partial_eval_rows(com.mat, pt[:com.log_r])          # (C, 4)
    transcript.absorb(u)
    n_cols = com.enc.shape[1]
    idx = transcript.challenge_indices(n_cols, params.queries)
    columns = np.asarray(com.enc[:, idx]).T                  # (t, R)
    paths = M.batch_open(com.tree, idx)
    return OpeningBundle(us=np.asarray(u)[None], u_prox=None,
                         columns=columns, paths=paths, batch_sc=sc)


def _gather_columns(root: np.ndarray, idx: np.ndarray, bundle: OpeningBundle,
                    store: Optional[ColumnStore], n_rows: int,
                    params: PCSParams) -> Optional[jnp.ndarray]:
    """Resolve the queried columns, either from inline paths or a store.

    In store mode the bundle MUST NOT carry inline columns/paths — otherwise
    an attestation could smuggle unverified columns past the multiproof."""
    if store is not None:
        if bundle.columns is not None or bundle.paths:
            return None
        return store.gather(root, idx, n_rows)
    if (not isinstance(bundle.columns, np.ndarray)
            or bundle.columns.shape != (len(idx), n_rows)
            or bundle.columns.dtype != np.uint32):
        return None
    if bundle.paths is None or len(bundle.paths) != len(idx):
        return None
    for j, path in zip(idx, bundle.paths):
        if path.index != int(j):
            return None
    cols = jnp.asarray(bundle.columns)                       # (t, R)
    if not M.verify_paths_batch(root, cols, bundle.paths):
        return None
    return cols


def verify_openings(root: np.ndarray, log_r: int, log_c: int,
                    points: Sequence[jnp.ndarray],
                    claimed_values: Sequence[jnp.ndarray],
                    bundle: OpeningBundle, transcript: Transcript,
                    params: PCSParams,
                    store: Optional[ColumnStore] = None) -> bool:
    if not isinstance(bundle, OpeningBundle):
        return False
    if len(points) >= 2:
        return _verify_openings_batched(root, log_r, log_c, points,
                                        claimed_values, bundle, transcript,
                                        params, store)
    R, C = 1 << log_r, 1 << log_c
    n_cols = C * params.blowup
    if bundle.batch_sc is not None:
        return False
    if (not isinstance(bundle.us, np.ndarray) or bundle.us.ndim != 3
            or bundle.us.shape != (len(points), C, 4)
            or bundle.us.dtype != np.uint32):
        return False
    if (not isinstance(bundle.u_prox, np.ndarray)
            or bundle.u_prox.shape != (C, 4)
            or bundle.u_prox.dtype != np.uint32):
        return False
    # 1. absorb u rows in order, checking the claimed evaluations
    enc_us = []
    bs = []
    for u_np, point, value in zip(bundle.us, points, claimed_values):
        u = jnp.asarray(u_np)
        transcript.absorb(u)
        a = eq_points(point[log_r:])
        got = fsum(F.f4mul(u, a), axis=0)
        if not np.array_equal(np.asarray(got), np.asarray(value)):
            return False
        bs.append(eq_points(point[:log_r]))                  # (R, 4)
        enc_us.append(_encode_f4_row(u, params.blowup))      # (n_cols, 4)
    # 2. proximity row
    rho = transcript.challenge_f4_vec(R)
    u_prox = jnp.asarray(bundle.u_prox)
    transcript.absorb(u_prox)
    enc_prox = _encode_f4_row(u_prox, params.blowup)
    # 3. queries — fully vectorized over the t query columns
    idx = transcript.challenge_indices(n_cols, params.queries)
    cols = _gather_columns(root, idx, bundle, store, R, params)
    if cols is None:
        return False
    cols4 = cols[:, :, None]                                 # (t, R, 1)
    idx_np = np.asarray(idx)
    for b, enc_u in zip(bs, enc_us):
        lhs = fsum(F.fmul(b[None], cols4), axis=1)           # (t, 4)
        if not np.array_equal(np.asarray(lhs),
                              np.asarray(enc_u[idx_np])):
            return False
    lhs = fsum(F.fmul(rho[None], cols4), axis=1)
    if not np.array_equal(np.asarray(lhs), np.asarray(enc_prox[idx_np])):
        return False
    return True


def _verify_openings_batched(root: np.ndarray, log_r: int, log_c: int,
                             points: Sequence[jnp.ndarray],
                             claimed_values: Sequence[jnp.ndarray],
                             bundle: OpeningBundle, transcript: Transcript,
                             params: PCSParams,
                             store: Optional[ColumnStore]) -> bool:
    R, C = 1 << log_r, 1 << log_c
    n_cols = C * params.blowup
    if not isinstance(bundle.batch_sc, SC.SumcheckProof):
        return False
    if bundle.u_prox is not None:
        return False
    if (not isinstance(bundle.us, np.ndarray)
            or bundle.us.shape != (1, C, 4)
            or bundle.us.dtype != np.uint32):
        return False
    # 1. fold the k claims with gamma; the sum-check proves
    #    sum_z M~(z) E(z) = sum_i gamma^i v_i
    for v in claimed_values:
        transcript.absorb(jnp.asarray(v))
    gamma = transcript.challenge_f4()
    s = _gamma_fold(claimed_values, gamma)
    if bundle.batch_sc.round_polys.shape[:1] != (log_r + log_c,):
        return False
    ok, pt, finals = SC.verify(s, bundle.batch_sc, 2, transcript)
    if not ok:
        return False
    # E(pt) the verifier computes itself — eq_eval is O(m) per point
    e_pt = _gamma_fold([eq_eval(jnp.asarray(p), pt) for p in points], gamma)
    if not np.array_equal(np.asarray(finals[1]), np.asarray(e_pt)):
        return False
    # 2. the single u row must reproduce M~(pt)
    u = jnp.asarray(bundle.us[0])
    transcript.absorb(u)
    got = fsum(F.f4mul(u, eq_points(pt[log_r:])), axis=0)
    if not np.array_equal(np.asarray(got), np.asarray(finals[0])):
        return False
    # 3. spot-check Enc(u) against the committed columns.  pt is
    #    transcript-random, so the tensor query doubles as the proximity
    #    test — no separate u_prox row.
    idx = transcript.challenge_indices(n_cols, params.queries)
    cols = _gather_columns(root, idx, bundle, store, R, params)
    if cols is None:
        return False
    b = eq_points(pt[:log_r])                                # (R, 4)
    enc_u = _encode_f4_row(u, params.blowup)                 # (n_cols, 4)
    lhs = fsum(F.fmul(b[None], cols[:, :, None]), axis=1)    # (t, 4)
    return bool(np.array_equal(np.asarray(lhs),
                               np.asarray(enc_u[np.asarray(idx)])))


def combine_f4_values(values: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """sum_k x^k * values[k] — recombine per-coefficient claims into Fp4."""
    acc = None
    for k, vk in enumerate(values):
        basis = F.f4zero(()).at[k].set(np.uint32(F.R_MOD_P))
        term = F.f4mul(jnp.asarray(vk), basis)
        acc = term if acc is None else F.f4add(acc, term)
    return acc
