"""Commitment chain + compositional soundness (paper §3, Theorem 3.1).

A ModelProof is the composite (pi_0 ... pi_{L-1}) plus the boundary
commitment roots (c_0 ... c_L). Verification checks:
  1. every layer proof verifies against its published weight root,
  2. adjacent proofs share boundary roots:  c_out(pi_l) == c_in(pi_{l+1})
     (Eq. 3 — this is what kills mix-and-match attacks),
  3. the claimed input/output commitments match the user's query binding.

`soundness_bound` reproduces the Thm 3.1 accounting for OUR per-layer
proof system (sum-checks over Fp4 + Ligero PCS + LogUp + Poseidon2), i.e.
eps_total <= sum_l eps_l + (L+2) * negl(lambda) with eps_l summed from the
component soundness errors below.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import blocks as B
from . import field as F
from . import layer_proof as LP
from . import pcs as PCS


@dataclasses.dataclass
class ModelProof:
    layer_proofs: List[LP.LayerProof]
    boundary_roots: List[np.ndarray]     # c_0 .. c_L
    wt_roots: List[np.ndarray]

    def size_bytes(self) -> int:
        return sum(p.size_bytes() for p in self.layer_proofs)


def prove_model(cfgs: Sequence[B.BlockCfg],
                weights_raw: Sequence[Dict[str, np.ndarray]],
                wt_commits: Sequence[LP.WeightCommit],
                x0: np.ndarray, params: PCS.PCSParams,
                layer_subset: Optional[Sequence[int]] = None,
                workers: int = 1) -> ModelProof:
    """Run the quantized forward chain and prove every (selected) layer.

    DEPRECATED shim: new callers should use ``repro.api.ProofService``,
    which keeps the engine + weight cache resident and returns a
    serializable Attestation.

    Thin wrapper over the staged ProverEngine (runtime/engine.py):
    quantized forward replay, one batched PCS commit over all boundary
    activations, then per-layer ProofJobs dispatched across ``workers``
    prover threads (layer proofs are independent given the commitments,
    paper §3.3).  Proving is Fiat-Shamir deterministic, so any worker
    count yields identical transcripts.
    """
    from repro.runtime.engine import ProverEngine  # runtime sits above core
    eng = ProverEngine(cfgs, weights_raw, params, wt_commits=wt_commits,
                       workers=workers)
    proof, _report = eng.prove(x0, layer_subset=layer_subset)
    return proof


def verify_model(cfgs: Sequence[B.BlockCfg], proof: ModelProof,
                 wt_roots: Sequence[np.ndarray], params: PCS.PCSParams,
                 in_root: Optional[np.ndarray] = None,
                 out_root: Optional[np.ndarray] = None) -> bool:
    """Full composite verification incl. the Eq. 3 adjacency checks."""
    # query binding
    if in_root is not None and not np.array_equal(
            proof.boundary_roots[0], in_root):
        return False
    if out_root is not None and not np.array_equal(
            proof.boundary_roots[-1], out_root):
        return False
    for lp in proof.layer_proofs:
        l = lp.layer_index
        # Eq. 3: commitment-chain adjacency
        if not np.array_equal(lp.in_root, proof.boundary_roots[l]):
            return False
        if not np.array_equal(lp.out_root, proof.boundary_roots[l + 1]):
            return False
        if not LP.verify_layer(cfgs[l], lp, wt_roots[l], params,
                               check_input_range=(l == 0)):
            return False
    return True


# ---------------------------------------------------------------------------
# Theorem 3.1 accounting for this proof system.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class SoundnessReport:
    eps_layer: float
    eps_total: float
    bits_layer: float
    bits_total: float
    components: Dict[str, float]


def layer_circuit_stats(cfg: B.BlockCfg) -> Dict[str, int]:
    """Conservative counts of soundness-relevant events per layer proof."""
    H, seq = cfg.heads, cfg.seq
    # the score and P.V matmuls of all heads are one argument each, over
    # log2(pad2(H)) head variables; grouped k/v heads keep one per head
    head_mm = 2 if cfg.kv_heads == H else 2 * H
    n_matmul = 3 + head_mm + 2 + (1 if cfg.family == "llama" else 0)
    n_sumchecks = 9 * n_matmul + 30 + (8 * H if cfg.family == "llama" else 0)
    max_vars = max((cfg.dff_pad * cfg.seq).bit_length(),
                   (H * seq * seq).bit_length(),
                   (B._pad2(H) * max(cfg.dh, seq)).bit_length()) + 3
    n_lookups = 5
    n_openings = 16
    n_relations = 40
    return dict(n_sumchecks=n_sumchecks, max_vars=max_vars,
                n_lookups=n_lookups, n_openings=n_openings,
                n_relations=n_relations,
                witness=8 * cfg.dff_pad * cfg.seq + 12 * H * seq * seq)


def soundness_bound(cfgs: Sequence[B.BlockCfg], params: PCS.PCSParams
                    ) -> SoundnessReport:
    """eps_total <= sum_l eps_l + (L+2) negl  (Thm 3.1), with eps_l from:

    * sum-checks: rounds * degree / |Fp4|      (Schwartz-Zippel per round)
    * LogUp: (witness + table) / |Fp4|         (pole collision on alpha)
    * linear relations: 1 claim point each: max_vars / |Fp4|
    * Ligero PCS: ((1+rho)/2 + b)^queries per opening session, where
      b = n_cols/(4P) is the per-index total-variation bias of the
      mod-n_cols reduction in Transcript.challenge_indices (a biased
      query misses a bad column with probability at most TV more than a
      uniform one, so b adds to the per-query miss probability);
      n_cols is conservatively the encoded width of the LARGEST
      commitment. The "index_bias" component reports the delta vs the
      ideal uniform sampler — fs_lint asserts it stays negligible.
    * Poseidon2 collision resistance: 2^-124 (capacity 248 bits, birthday)
    """
    f4 = float(F.P) ** 4
    eps_total = 0.0
    comp = dict(sumcheck=0.0, logup=0.0, relations=0.0, pcs=0.0,
                index_bias=0.0)
    for cfg in cfgs:
        st = layer_circuit_stats(cfg)
        e_sc = st["n_sumchecks"] * st["max_vars"] * 4 / f4
        e_lu = st["n_lookups"] * (st["witness"] + 2 ** 16) / f4
        e_rel = st["n_relations"] * st["max_vars"] / f4
        rho = 1.0 / params.blowup
        n_cols_max = params.blowup * (
            1 << ((st["witness"].bit_length() + 1) // 2))
        bias = n_cols_max / (4.0 * float(F.P))
        e_pcs = st["n_openings"] * ((1 + rho) / 2) ** params.queries
        e_bias = (st["n_openings"]
                  * ((1 + rho) / 2 + bias) ** params.queries) - e_pcs
        comp["sumcheck"] += e_sc
        comp["logup"] += e_lu
        comp["relations"] += e_rel
        comp["pcs"] += e_pcs
        comp["index_bias"] += e_bias
        eps_total += e_sc + e_lu + e_rel + e_pcs + e_bias
    L = len(cfgs)
    negl_hash = (L + 2) * 2.0 ** -124
    eps_total += negl_hash
    comp["hash"] = negl_hash
    eps_layer = eps_total / max(L, 1)
    return SoundnessReport(
        eps_layer=eps_layer, eps_total=eps_total,
        bits_layer=-math.log2(eps_layer) if eps_layer else float("inf"),
        bits_total=-math.log2(eps_total) if eps_total else float("inf"),
        components=comp)
