"""Pallas kernels vs pure-jnp oracles: exact equality over shape sweeps."""
import numpy as np
import pytest

from repro.core import field as F
from repro.kernels import ops, ref


@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (8, 8, 8, 8, 8, 8),
    (16, 32, 8, 8, 8, 16),
    (32, 16, 16, 16, 8, 8),
])
def test_modmatmul_shapes(rng, M, K, N, bm, bn, bk):
    a = F.f_from_int(rng.integers(0, F.P, (M, K)))
    b = F.f_from_int(rng.integers(0, F.P, (K, N)))
    got = ops.modmatmul(a, b, bm=bm, bn=bn, bk=bk)
    want = ref.modmatmul_ref(a, b)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,block", [(8, 8), (32, 8), (64, 16)])
def test_poseidon2_batch(rng, n, block):
    """Whole-batch and per-block permutes agree with the oracle: the
    kernel's row padding never leaks between states."""
    st = F.f_from_int(rng.integers(0, F.P, (n, 16)))
    got = ops.poseidon2_permute(st)
    want = ref.permute_ref(st)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    blocks = [ops.poseidon2_permute(st[i:i + block])
              for i in range(0, n, block)]
    assert np.array_equal(np.concatenate([np.asarray(b) for b in blocks]),
                          np.asarray(want))


@pytest.mark.parametrize("rows,n,inverse", [
    (2, 16, False), (4, 64, False), (4, 64, True), (8, 128, False)])
def test_ntt_rows(rng, rows, n, inverse):
    x = F.f_from_int(rng.integers(0, F.P, (rows, n)))
    got = ops.ntt(x, inverse=inverse)
    want = ref.ntt_ref(x, inverse=inverse)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_ntt_inverse_roundtrip(rng):
    x = F.f_from_int(rng.integers(0, F.P, (2, 32)))
    y = ops.ntt(ops.ntt(x), inverse=True)
    assert np.array_equal(np.asarray(y), np.asarray(x))


@pytest.mark.parametrize("n,d,block", [(32, 1, 8), (64, 2, 16), (64, 3, 32)])
def test_sumcheck_fold(rng, n, d, block):
    factors = [F.f4_from_base(F.f_from_int(rng.integers(0, F.P, n)))
               for _ in range(d)]
    c = F.f4_from_base(F.fconst(int(rng.integers(1, F.P))))
    g, folded = ops.sumcheck_fold(factors, c, block=block)
    g_r, folded_r = ref.fold_round_ref(factors, c)
    assert np.array_equal(np.asarray(g), np.asarray(g_r))
    for a, b in zip(folded, folded_r):
        assert np.array_equal(np.asarray(a), np.asarray(b))
