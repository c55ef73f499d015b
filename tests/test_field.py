"""Field arithmetic: exactness vs Python-int ground truth + ring axioms."""
import numpy as np
from hypothesis_compat import given, settings, st

from repro.core import field as F

fp_elem = st.integers(min_value=0, max_value=F.P - 1)


def _mont(xs):
    return F.f_from_int(xs)


@given(st.lists(fp_elem, min_size=1, max_size=64), st.lists(fp_elem, min_size=1, max_size=64))
@settings(max_examples=50, deadline=None)
def test_mul_matches_int(a, b):
    n = min(len(a), len(b))
    a, b = np.array(a[:n], np.int64), np.array(b[:n], np.int64)
    got = F.f_to_int(F.fmul(_mont(a), _mont(b)))
    np.testing.assert_array_equal(got, (a * b) % F.P)


@given(st.lists(fp_elem, min_size=1, max_size=64), st.lists(fp_elem, min_size=1, max_size=64))
@settings(max_examples=50, deadline=None)
def test_add_sub_match_int(a, b):
    n = min(len(a), len(b))
    a, b = np.array(a[:n], np.int64), np.array(b[:n], np.int64)
    np.testing.assert_array_equal(F.f_to_int(F.fadd(_mont(a), _mont(b))), (a + b) % F.P)
    np.testing.assert_array_equal(F.f_to_int(F.fsub(_mont(a), _mont(b))), (a - b) % F.P)


def test_edge_values():
    edge = np.array([0, 1, 2, F.P - 1, F.P - 2, 0xFFFF, 0x10000, 2**30], np.int64)
    A, B = np.meshgrid(edge, edge)
    a, b = A.ravel(), B.ravel()
    np.testing.assert_array_equal(F.f_to_int(F.fmul(_mont(a), _mont(b))), (a * b) % F.P)
    np.testing.assert_array_equal(F.f_to_int(F.fadd(_mont(a), _mont(b))), (a + b) % F.P)
    np.testing.assert_array_equal(F.f_to_int(F.fsub(_mont(a), _mont(b))), (a - b) % F.P)
    np.testing.assert_array_equal(F.f_to_int(F.fneg(_mont(a))), (-a) % F.P)


def test_inverse():
    rng = np.random.default_rng(0)
    a = rng.integers(1, F.P, size=128, dtype=np.int64)
    inv = F.f_to_int(F.finv(_mont(a)))
    np.testing.assert_array_equal((a * inv) % F.P, np.ones_like(a))


def test_pow():
    rng = np.random.default_rng(1)
    a = rng.integers(0, F.P, size=32, dtype=np.int64)
    for e in (0, 1, 2, 7, F.P - 2, (F.P - 1) // 2):
        got = F.f_to_int(F.fpow(_mont(a), e))
        want = np.array([pow(int(x), e, F.P) for x in a], np.int64)
        np.testing.assert_array_equal(got, want)


def test_generator_order():
    # 31 generates the full multiplicative group.
    g = F.fconst(F.GENERATOR)
    assert F.f_to_int(F.fpow(g, F.P - 1)) == 1
    assert F.f_to_int(F.fpow(g, (F.P - 1) // 2)) != 1


# ----------------------------------------------------------------- Fp4 -----
def _rand_f4(rng, shape):
    return F.f4_from_int(rng.integers(0, F.P, size=tuple(shape) + (4,), dtype=np.int64))


def test_f4_mul_ring_axioms():
    rng = np.random.default_rng(2)
    a, b, c = (_rand_f4(rng, (16,)) for _ in range(3))
    # commutativity / associativity / distributivity
    np.testing.assert_array_equal(F.f_to_int(F.f4mul(a, b)), F.f_to_int(F.f4mul(b, a)))
    np.testing.assert_array_equal(
        F.f_to_int(F.f4mul(F.f4mul(a, b), c)), F.f_to_int(F.f4mul(a, F.f4mul(b, c))))
    np.testing.assert_array_equal(
        F.f_to_int(F.f4mul(a, F.f4add(b, c))),
        F.f_to_int(F.f4add(F.f4mul(a, b), F.f4mul(a, c))))


def test_f4_identity_and_embed():
    rng = np.random.default_rng(3)
    a = _rand_f4(rng, (8,))
    one = F.f4one((8,))
    np.testing.assert_array_equal(F.f_to_int(F.f4mul(a, one)), F.f_to_int(a))
    # base embedding multiplies like scalars
    x = rng.integers(0, F.P, size=8, dtype=np.int64)
    xe = F.f4_from_base(F.f_from_int(x))
    prod = F.f4mul(a, xe)
    want = (F.f_to_int(a) * x[:, None]) % F.P
    np.testing.assert_array_equal(F.f_to_int(prod), want)


def test_f4_inverse():
    rng = np.random.default_rng(4)
    a = _rand_f4(rng, (8,))
    inv = F.f4inv(a)
    prod = F.f_to_int(F.f4mul(a, inv))
    want = np.zeros((8, 4), np.int64)
    want[:, 0] = 1
    np.testing.assert_array_equal(prod, want)


def test_f4_is_field_no_zero_divisors_smoke():
    rng = np.random.default_rng(5)
    a, b = _rand_f4(rng, (64,)), _rand_f4(rng, (64,))
    prod = F.f_to_int(F.f4mul(a, b))
    assert not np.any(np.all(prod == 0, axis=-1))
