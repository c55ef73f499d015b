"""Per-layer proof objects: pi_l proving h_l = f_l(h_{l-1}; W_l)  (Eq. 2).

A LayerProof binds:
  * the boundary commitment roots c_{l-1}, c_l (the paper's commitment
    chain, Eq. 3 — chain.py checks adjacency),
  * the published weight commitment root for layer l (from setup), and
  * the proof tape produced by the block argument (circuit.py gadgets).

Weight commitments and their range proofs are produced ONCE at setup and
amortized across queries — the paper's ~37 s/layer setup vs ~6 s/layer
proving split.
"""
from __future__ import annotations

import dataclasses
import pickle
from typing import Dict, List, Optional

import numpy as np

from . import blocks as B
from . import circuit as C
from . import pcs as PCS
from .transcript import Transcript


@dataclasses.dataclass
class BoundaryCommit:
    """Commitment to one inter-layer activation h_l (limb slices)."""
    com: Optional[PCS.Commitment]      # prover side only
    ints: Optional[np.ndarray]
    root: np.ndarray
    n: int
    slices: Dict[str, C.Slice]
    layout: Dict


@dataclasses.dataclass
class WeightCommit:
    com: Optional[PCS.Commitment]
    ints: Optional[np.ndarray]
    root: np.ndarray
    n: int
    slices: Dict[str, C.Slice]
    layout: Dict
    range_tape: List                   # standalone range-proof (setup)


@dataclasses.dataclass
class LayerProof:
    layer_index: int
    in_root: np.ndarray                # c_{l-1}
    out_root: np.ndarray               # c_l
    wt_root: np.ndarray
    tape: List

    def size_bytes(self) -> int:
        return len(pickle.dumps(self.tape))


# ---------------------------------------------------------------------------
# Setup / commitment helpers.
# ---------------------------------------------------------------------------
def pack_boundary(cfg: B.BlockCfg, x: Optional[np.ndarray],
                  name: str = "bnd"):
    """Public boundary layout + packed witness ints (no commitment yet)."""
    wb = C.WitnessBuilder(name)
    layout = B.declare_boundary(cfg, wb, x)
    slices, packed, total = wb.pack()
    return layout, slices, packed, total


def commit_boundary(cfg: B.BlockCfg, x: Optional[np.ndarray],
                    params: PCS.PCSParams,
                    name: str = "bnd") -> BoundaryCommit:
    layout, slices, packed, total = pack_boundary(cfg, x, name)
    if packed is None:
        return BoundaryCommit(None, None, None, total, slices, layout)
    import repro.core.field as F
    com = PCS.commit(F.f_from_int(packed), params)
    return BoundaryCommit(com, packed, com.root, total, slices, layout)


def commit_boundaries(cfgs: List[B.BlockCfg], xs: List[np.ndarray],
                      params: PCS.PCSParams,
                      name: str = "bnd") -> List[BoundaryCommit]:
    """Commit all boundary activations through one vectorized PCS path.

    Same-width boundaries (the common case: every inter-layer activation of
    a homogeneous model) are stacked and committed by a single batched
    NTT + Merkle pass (PCS.commit_batch) instead of L+1 separate commits;
    mixed-width chains fall back to per-width groups.  Roots are
    bit-identical to sequential ``commit_boundary`` calls.
    """
    import repro.core.field as F
    packs = [pack_boundary(cfg, x, name) for cfg, x in zip(cfgs, xs)]
    out: List[Optional[BoundaryCommit]] = [None] * len(packs)
    groups: Dict[int, List[int]] = {}
    for i, (layout, slices, packed, total) in enumerate(packs):
        if packed is None:
            out[i] = BoundaryCommit(None, None, None, total, slices, layout)
        else:
            groups.setdefault(packed.shape[0], []).append(i)
    for idxs in groups.values():
        coms = PCS.commit_batch(
            [F.f_from_int(packs[i][2]) for i in idxs], params)
        for i, com in zip(idxs, coms):
            layout, slices, packed, total = packs[i]
            out[i] = BoundaryCommit(com, packed, com.root, total, slices,
                                    layout)
    return out


def commit_weights(cfg: B.BlockCfg, w: Optional[Dict[str, np.ndarray]],
                   params: PCS.PCSParams, name: str = "wt") -> WeightCommit:
    """Commit layer weights (no range proof — see weight_range_proof)."""
    wb = C.WitnessBuilder(name)
    layout = B.declare_weights(cfg, wb, w)
    slices, packed, total = wb.pack()
    if packed is None:
        return WeightCommit(None, None, None, total, slices, layout, [])
    import repro.core.field as F
    com = PCS.commit(F.f_from_int(packed), params)
    return WeightCommit(com, packed, com.root, total, slices, layout, [])


def weight_range_proof(wt: WeightCommit, params: PCS.PCSParams,
                       name: str = "wt") -> List:
    """Standalone range proof over a committed weight vector (setup cost;
    runtime/engine.py caches it by weight root to amortize across queries)."""
    tr = Transcript("nanozk.wt.range")
    ctx = C.ProverCtx(tr, params)
    ctx.attach(name, wt.com, wt.ints)
    C.g_range8(ctx, name, wt.n)
    C.flush_lookups(ctx)
    ctx.finalize()
    return ctx.tape


def setup_weights(cfg: B.BlockCfg, w: Optional[Dict[str, np.ndarray]],
                  params: PCS.PCSParams, name: str = "wt") -> WeightCommit:
    """Commit layer weights + produce the amortized range proof."""
    wt = commit_weights(cfg, w, params, name)
    if wt.com is not None:
        wt.range_tape = weight_range_proof(wt, params, name)
    return wt


def verify_weight_setup(cfg: B.BlockCfg, root: np.ndarray, range_tape: List,
                        params: PCS.PCSParams, name: str = "wt") -> bool:
    wb = C.WitnessBuilder(name)
    B.declare_weights(cfg, wb, None)
    _, _, total = wb.pack()
    tr = Transcript("nanozk.wt.range")
    ctx = C.VerifierCtx(tr, params, range_tape)
    ctx.attach(name, root, total)
    try:
        C.g_range8(ctx, name, total)
        C.flush_lookups(ctx)
        ctx.finalize()
    except C.ProofError:
        return False
    return True


# ---------------------------------------------------------------------------
# Layer prove / verify.
# ---------------------------------------------------------------------------
def _boundary_views(bc: BoundaryCommit, com_name: str) -> C.Affine:
    slices = {k: dataclasses.replace(v, com=com_name)
              for k, v in bc.slices.items()}
    return B.Views(bc.layout, slices).limb("act")


def prove_layer(cfg: B.BlockCfg, layer_index: int, wt: WeightCommit,
                b_in: BoundaryCommit, b_out: BoundaryCommit,
                trace: Dict[str, np.ndarray], params: PCS.PCSParams,
                check_input_range: bool = False) -> LayerProof:
    """The layer's proof, in four phases timed as spans (``repro.spans``):
    witness, argument, lookups and openings."""
    # imported here, not at the top: a Pallas kernel traced from the
    # functions above carries their source lines in its compile-cache key
    from repro import spans
    with spans.span("layer.witness"):
        tr = Transcript("nanozk.layer")
        tr.absorb_int(layer_index)
        ctx = C.ProverCtx(tr, params)
        ctx.attach("wt", wt.com, wt.ints)
        ctx.attach("b_in", b_in.com, b_in.ints)
        ctx.attach("b_out", b_out.com, b_out.ints)

        wb = C.WitnessBuilder("aux")
        prepared = B.prepare_trace(cfg, trace)
        layout = B.declare_aux(cfg, wb, prepared)
        slices = wb.build(ctx)
        V = B.Views(layout, slices)
        Vw = B.Views(wt.layout, wt.slices)
        x_view = _boundary_views(b_in, "b_in")
        y_view = _boundary_views(b_out, "b_out")
    with spans.span("layer.argument"):
        B.block_argument(ctx, cfg, V, Vw, x_view, y_view,
                         lut_ints=B.lut_int_arrays(cfg, trace))
        wb.run_checks(ctx, slices)
    with spans.span("layer.lookups"):
        C.g_range8(ctx, "b_out", b_out.n)
        if check_input_range:
            C.g_range8(ctx, "b_in", b_in.n)
        C.flush_lookups(ctx)
    with spans.span("layer.openings"):
        ctx.finalize()
    for name, n in tape_counts(ctx.tape).items():
        spans.count(f"tape.{name}", n)
    return LayerProof(layer_index=layer_index, in_root=b_in.root,
                      out_root=b_out.root, wt_root=wt.root, tape=ctx.tape)


def verify_layer(cfg: B.BlockCfg, proof: LayerProof, wt_root: np.ndarray,
                 params: PCS.PCSParams,
                 check_input_range: bool = False,
                 store: Optional[PCS.ColumnStore] = None) -> bool:
    if not np.array_equal(proof.wt_root, wt_root):
        return False
    tr = Transcript("nanozk.layer")
    tr.absorb_int(proof.layer_index)
    ctx = C.VerifierCtx(tr, params, proof.tape, store=store)
    # reconstruct public layouts
    wb_wt = C.WitnessBuilder("wt")
    wt_layout = B.declare_weights(cfg, wb_wt, None)
    wt_slices, _, wt_total = wb_wt.pack()
    wb_b = C.WitnessBuilder("bnd")
    b_layout = B.declare_boundary(cfg, wb_b, None)
    b_slices, _, b_total = wb_b.pack()
    ctx.attach("wt", proof.wt_root, wt_total)
    ctx.attach("b_in", proof.in_root, b_total)
    ctx.attach("b_out", proof.out_root, b_total)

    wb = C.WitnessBuilder("aux")
    layout = B.declare_aux(cfg, wb, None)
    try:
        slices = wb.build(ctx)
        V = B.Views(layout, slices)
        Vw = B.Views(wt_layout, {k: dataclasses.replace(v, com="wt")
                                 for k, v in wt_slices.items()})
        bv_in = B.Views(b_layout, {k: dataclasses.replace(v, com="b_in")
                                   for k, v in b_slices.items()})
        bv_out = B.Views(b_layout, {k: dataclasses.replace(v, com="b_out")
                                    for k, v in b_slices.items()})
        B.block_argument(ctx, cfg, V, Vw, bv_in.limb("act"),
                         bv_out.limb("act"))
        wb.run_checks(ctx, slices)
        C.g_range8(ctx, "b_out", b_total)
        if check_input_range:
            C.g_range8(ctx, "b_in", b_total)
        C.flush_lookups(ctx)
        ctx.finalize()
    except C.ProofError:
        return False
    return True


def tape_counts(tape: List) -> Dict[str, int]:
    """A finished layer tape's sum-checks (``SumcheckProof`` objects, the
    openings' claim-folding ones included) and claimed values (``"val"``
    entries: leaf claims and claimed sums)."""
    from .sumcheck import SumcheckProof
    objs = [it[1] if it[0] == "obj" else it[2].batch_sc
            for it in tape if it[0] in ("obj", "open")]
    return {"sumchecks": sum(isinstance(o, SumcheckProof) for o in objs),
            "vals": sum(1 for it in tape if it[0] == "val")}
