"""The attention matmuls of all heads proved as one argument each.

The score matmul (Q_h K_h^T) and the P.V matmul of every head are one
head-batched int-matmul argument (``circuit.g_int_matmul_heads``), with
a rescale over the whole ``sidx`` / ``err_s`` and ``O`` / ``err_o``
witness.  Blocks of 2, 3 and 4 heads round-trip (3 heads pad to 4, so
the padding head's witness meets the batched rescales); one off-by-one
rescale error in a single head is refused by the batched relation, on
the prover and on the verifier; and the number of sum-checks on a layer
tape no longer grows with the head count.
"""
import numpy as np
import pytest

from repro.core import blocks as B
from repro.core import circuit as C
from repro.core import layer_proof as LP
from repro.core import pcs as PCS

PARAMS = PCS.PCSParams(blowup=4, queries=2)


def _cfg(heads: int) -> B.BlockCfg:
    return B.BlockCfg(family="gpt2", d=16, dff=32, heads=heads,
                      kv_heads=heads, dh=4, seq=4)


def _setup(heads: int):
    cfg = _cfg(heads)
    rng = np.random.default_rng(100 + heads)
    w = B.init_weights(cfg, rng)
    wt = LP.setup_weights(cfg, w, PARAMS)
    x = np.clip(np.round(rng.normal(0, 0.5, (cfg.d_pad, cfg.seq)) * 256),
                -32768, 32767).astype(np.int64)
    y, trace = B.block_forward(cfg, w, x)
    return (cfg, wt, LP.commit_boundary(cfg, x, PARAMS),
            LP.commit_boundary(cfg, y, PARAMS), trace)


@pytest.fixture(scope="module")
def blocks():
    """heads -> (cfg, weight commit, boundary in, boundary out, trace),
    each built once for the module."""
    cache = {}

    def get(heads):
        if heads not in cache:
            cache[heads] = _setup(heads)
        return cache[heads]
    return get


def _prove(setup, trace=None):
    cfg, wt, b_in, b_out, tr = setup
    return LP.prove_layer(cfg, 0, wt, b_in, b_out,
                          tr if trace is None else trace, PARAMS)


@pytest.fixture(scope="module")
def sumchecks():
    """Sum-checks on each head count's layer tape, as the tests find them."""
    return {}


@pytest.mark.parametrize("heads", [2, 3, 4])
def test_block_round_trips(blocks, sumchecks, heads):
    setup = blocks(heads)
    cfg, wt = setup[0], setup[1]
    proof = _prove(setup)
    assert LP.verify_layer(cfg, proof, wt.root, PARAMS)
    # one score and one P.V sum-check for all heads: the tape holds as
    # many sum-checks at every head count
    sumchecks[heads] = LP.tape_counts(proof.tape)["sumchecks"]
    assert len(set(sumchecks.values())) == 1, sumchecks


def _tamper(trace, key, row):
    """``trace`` with one entry of ``key`` off by one, still in range."""
    tr = dict(trace)
    arr = tr[key].copy()
    flat = arr.reshape(-1, arr.shape[-1])
    flat[row, 1] += 1 if flat[row, 1] == 0 else -1
    tr[key] = flat.reshape(arr.shape)
    return tr


@pytest.mark.parametrize("head", [1, 2], ids=["middle", "last-real"])
@pytest.mark.parametrize("key,what", [("err_s", "scores"),
                                      ("err_o", "attn out")])
def test_one_head_off_by_one_is_refused(blocks, monkeypatch, key, what,
                                        head):
    setup = blocks(3)                 # heads 0-2 real, head 3 padding
    cfg = setup[0]
    # err_s is (heads, seq, seq), err_o (heads * dh, seq): a row of head
    row = head * cfg.seq + 2 if key == "err_s" else head * cfg.dh + 2
    bad = _tamper(setup[4], key, row)
    with pytest.raises(C.ProofError, match=f"relation failed: {what}$"):
        _prove(setup, bad)
    # a prover that skips its own checks: the verifier refuses the tape
    # at the same batched relation, and at no earlier one
    monkeypatch.setattr(C.ProverCtx, "check_eq", lambda *a: None)
    proof = _prove(setup, bad)
    failed = []
    check = C._Ctx.check_eq

    def spy(self, a, b, name):
        try:
            check(self, a, b, name)
        except C.ProofError:
            failed.append(name)
            raise
    monkeypatch.setattr(C.VerifierCtx, "check_eq", spy)
    assert not LP.verify_layer(cfg, proof, setup[1].root, PARAMS)
    assert failed == [what]
