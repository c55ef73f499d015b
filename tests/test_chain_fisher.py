"""Commitment chain (Thm 3.1) + Fisher selection tests.

Includes the mix-and-match attack: a valid layer proof from a DIFFERENT
computation must be rejected by the Eq. 3 adjacency check.
"""
import numpy as np
import pytest

from repro.core import blocks as B
from repro.core import chain as CH
from repro.core import fisher as FI
from repro.core import layer_proof as LP
from repro.core import pcs as PCS

CFG = B.BlockCfg(family="gpt2", d=16, dff=32, heads=2, kv_heads=2, dh=8,
                 seq=8)


@pytest.fixture(scope="module")
def two_layer_setup():
    params = PCS.PCSParams(blowup=4, queries=8)
    rng = np.random.default_rng(1)
    cfgs = [CFG, CFG]
    weights = [B.init_weights(CFG, rng) for _ in range(2)]
    commits = [LP.setup_weights(CFG, w, params) for w in weights]
    x0 = np.clip(np.round(rng.normal(0, 0.5, (CFG.d_pad, CFG.seq)) * 256),
                 -32768, 32767).astype(np.int64)
    proof = CH.prove_model(cfgs, weights, commits, x0, params)
    return params, cfgs, weights, commits, x0, proof


def test_model_proof_verifies(two_layer_setup):
    params, cfgs, weights, commits, x0, proof = two_layer_setup
    assert CH.verify_model(cfgs, proof, [c.root for c in commits], params,
                           in_root=proof.boundary_roots[0],
                           out_root=proof.boundary_roots[-1])


def test_mix_and_match_rejected(two_layer_setup):
    """Paper §3.1: swapping in a valid proof from another run must fail
    the commitment-chain adjacency check (Eq. 3)."""
    params, cfgs, weights, commits, x0, proof = two_layer_setup
    rng = np.random.default_rng(9)
    x_other = np.clip(np.round(rng.normal(0, 0.5,
                                          (CFG.d_pad, CFG.seq)) * 256),
                      -32768, 32767).astype(np.int64)
    other = CH.prove_model(cfgs, weights, commits, x_other, params)
    # splice layer 1's proof from the other (valid!) run
    import dataclasses
    frank = dataclasses.replace(
        proof, layer_proofs=[proof.layer_proofs[0],
                             other.layer_proofs[1]])
    assert not CH.verify_model(cfgs, frank, [c.root for c in commits],
                               params)
    # each spliced proof IS individually valid — the chain is what fails
    assert LP.verify_layer(cfgs[1], other.layer_proofs[1],
                           commits[1].root, params)


def test_wrong_weight_root_rejected(two_layer_setup):
    params, cfgs, weights, commits, x0, proof = two_layer_setup
    bad_roots = [commits[1].root, commits[0].root]   # swapped
    assert not CH.verify_model(cfgs, proof, bad_roots, params)


def test_three_times_power_of_two_widths_prove():
    """GPT-2 small's widths are 3 x 2^k (d=768, dff=3072, 12 heads).  A
    toy block of that shape proves and verifies: its softmax witness is
    padded to 4 heads, and the padding head satisfies the relations
    batched over heads."""
    cfg = B.BlockCfg(family="gpt2", d=24, dff=96, heads=3, kv_heads=3,
                     dh=8, seq=8)
    params = PCS.PCSParams(blowup=4, queries=2)
    rng = np.random.default_rng(2)
    weights = [B.init_weights(cfg, rng)]
    commits = [LP.setup_weights(cfg, weights[0], params)]
    x0 = np.clip(np.round(rng.normal(0, 0.5, (cfg.d_pad, cfg.seq)) * 256),
                 -32768, 32767).astype(np.int64)
    proof = CH.prove_model([cfg], weights, commits, x0, params)
    assert CH.verify_model([cfg], proof, [commits[0].root], params,
                           in_root=proof.boundary_roots[0],
                           out_root=proof.boundary_roots[-1])


def test_soundness_bound_accounting():
    params = PCS.PCSParams(blowup=4, queries=64)
    rep = CH.soundness_bound([CFG] * 32, params)
    # Thm 3.1 analogue: total error negligible, dominated by PCS queries
    assert rep.eps_total < 2 ** -20
    assert rep.bits_total > 20
    # scaling: 2x layers ~ 2x epsilon (union bound)
    rep2 = CH.soundness_bound([CFG] * 64, params)
    assert rep2.eps_total > rep.eps_total
    assert rep2.eps_total < 3 * rep.eps_total


def test_fisher_selection_strategies():
    imp = np.array([10.0, 8.0, 0.5, 0.4, 0.3, 0.2, 0.1, 0.05])
    scores = FI.FisherScores(per_layer_trace=imp,
                             per_layer_params=np.ones(8), importance=imp)
    top = FI.select_fisher(scores, 4)
    assert top == [0, 1, 2, 3]
    cov_f = FI.importance_coverage(scores, top)
    cov_u = FI.importance_coverage(scores, FI.select_uniform(8, 4))
    covs_r = [FI.importance_coverage(scores, FI.select_random(8, 4, s))
              for s in range(5)]
    assert cov_f >= max(covs_r)          # fisher >= random on this profile
    assert cov_f > cov_u
    assert cov_f > 0.95
    # fisher + random audit covers at least the fisher mass
    aud = FI.fisher_plus_random(scores, 3, 2, seed=0)
    assert set(FI.select_fisher(scores, 3)) <= set(aud)
    assert len(aud) == 5
