"""Sum-check + PCS + matmul-claim round-trips and tamper rejection."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import field as F
from repro.core import matmul_proof as MM
from repro.core import pcs as PCS
from repro.core import sumcheck as SC
from repro.core.mle import fsum, mle_eval_base, mle_eval_f4
from repro.core.transcript import Transcript


@pytest.mark.parametrize("n,d", [(8, 1), (16, 2), (32, 3)])
def test_sumcheck_roundtrip(rng, n, d):
    factors = [F.f4_from_base(F.f_from_int(rng.integers(0, F.P, n)))
               for _ in range(d)]
    prod = factors[0]
    for f in factors[1:]:
        prod = F.f4mul(prod, f)
    s = fsum(prod, axis=0)
    tr_p = Transcript("t")
    proof, pt = SC.prove(factors, tr_p)
    tr_v = Transcript("t")
    ok, pt_v, finals = SC.verify(s, proof, d, tr_v)
    assert ok and np.array_equal(np.asarray(pt), np.asarray(pt_v))
    for i, f in enumerate(factors):
        assert np.array_equal(np.asarray(mle_eval_f4(f, jnp.asarray(pt))),
                              finals[i])


def test_sumcheck_wrong_sum_rejected(rng):
    f = F.f4_from_base(F.f_from_int(rng.integers(0, F.P, 16)))
    tr_p = Transcript("t")
    proof, _ = SC.prove([f], tr_p)
    bad = F.f4add(fsum(f, axis=0), F.f4one(()))
    ok, *_ = SC.verify(bad, proof, 1, Transcript("t"))
    assert not ok


def test_pcs_roundtrip_and_tamper(rng, params):
    v = F.f_from_int(rng.integers(0, F.P, 64))
    com = PCS.commit(v, params)
    pts = [jnp.asarray(F.f4_from_base(F.f_from_int(
        rng.integers(0, F.P, 6)))) for _ in range(2)]
    vals = [PCS.eval_at(com, p) for p in pts]
    tr_p, tr_v = Transcript("o"), Transcript("o")
    bundle = PCS.prove_openings(com, pts, tr_p, params)
    assert PCS.verify_openings(com.root, com.log_r, com.log_c, pts, vals,
                               bundle, tr_v, params)
    # direct MLE agreement
    for p, val in zip(pts, vals):
        assert np.array_equal(np.asarray(mle_eval_base(v, p)),
                              np.asarray(val))
    # tampered claimed value
    bad = [vals[0], jnp.asarray(np.asarray(vals[1]) ^ 1)]
    assert not PCS.verify_openings(com.root, com.log_r, com.log_c, pts,
                                   bad, bundle, Transcript("o"), params)
    # tampered column data
    import dataclasses
    cols = bundle.columns.copy()
    cols[0, 0] ^= 1
    bad_bundle = dataclasses.replace(bundle, columns=cols)
    assert not PCS.verify_openings(com.root, com.log_r, com.log_c, pts,
                                   vals, bad_bundle, Transcript("o"),
                                   params)


@pytest.mark.parametrize("chunk_log", [16, 2])
def test_build_e_vec_matches_naive_fold(rng, monkeypatch, chunk_log):
    """The slice-wise e_vec equals sum_i gamma^i eq(p_i, .) exactly, for
    const-prefixed (slice) and full points, with groups written whole
    (chunk_log 16) or in 2^2-long chunks."""
    from repro.core.mle import eq_points
    monkeypatch.setattr(PCS, "_E_CHUNK_LOG", chunk_log)
    PCS._bucket_e_impl.clear_cache()
    m = 7
    one, zero = F.f_from_int([1, 0, 0, 0]), F.f_from_int([0, 0, 0, 0])

    def point(prefix):
        rnd = F.f_from_int(rng.integers(0, F.P, (m - len(prefix), 4)))
        bits = [one if b else zero for b in prefix]
        return np.asarray(jnp.concatenate([jnp.stack(bits), rnd])
                          if bits else rnd)

    pts = [point(p) for p in ((), (1,), (1,), (0, 1), (1, 1, 0), ())]
    gamma = F.f_from_int(rng.integers(0, F.P, 4))
    want, w = jnp.zeros((1 << m, 4), jnp.uint32), F.f4one(())
    for p in pts:
        want = F.f4add(want, F.f4mul(jnp.broadcast_to(w, want.shape),
                                     eq_points(jnp.asarray(p))))
        w = F.f4mul(w, gamma)
    got = PCS._build_e_vec(1 << m, pts, gamma)
    PCS._bucket_e_impl.clear_cache()
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_matmul_claims_match_direct_mle(rng):
    n, k, m = 8, 16, 4
    A = rng.integers(-50, 50, (n, k))
    B = rng.integers(-50, 50, (k, m))
    C = A @ B
    Af, Bf, Cf = (F.f_from_int(x) for x in (A, B, C))
    pf, _ = MM.prove("A", Af.reshape(n, k), "B", Bf.reshape(k, m),
                     "C", Cf.reshape(n, m), Transcript("mm"))
    ok, claims = MM.verify(pf, (n, k, m), ("A", "B", "C"),
                           Transcript("mm"))
    assert ok
    flat = {"A": Af.reshape(-1), "B": Bf.reshape(-1), "C": Cf.reshape(-1)}
    for cl in claims:
        got = mle_eval_base(flat[cl.tensor], jnp.asarray(cl.point))
        assert np.array_equal(np.asarray(got), cl.value)


def test_matmul_wrong_product_rejected(rng):
    n, k, m = 4, 8, 4
    A = rng.integers(-50, 50, (n, k))
    B = rng.integers(-50, 50, (k, m))
    C = A @ B
    C[0, 0] += 1
    Af, Bf, Cf = (F.f_from_int(x) for x in (A, B, C))
    pf, _ = MM.prove("A", Af.reshape(n, k), "B", Bf.reshape(k, m),
                     "C", Cf.reshape(n, m), Transcript("mm"))
    ok, claims = MM.verify(pf, (n, k, m), ("A", "B", "C"),
                           Transcript("mm"))
    # the sumcheck itself verifies, but the C claim no longer matches
    # the true C's MLE — a verifier discharging claims catches it.
    flat = {"A": Af.reshape(-1), "B": Bf.reshape(-1), "C": Cf.reshape(-1)}
    # prover computed honest claims of a FALSE statement: at least one
    # claim must disagree with the committed tensors
    true_C = F.f_from_int((A @ B))
    flat["C"] = true_C.reshape(-1)
    matches_true = all(
        np.array_equal(
            np.asarray(mle_eval_base(flat[cl.tensor], jnp.asarray(cl.point))),
            cl.value) for cl in claims)
    assert not (ok and matches_true)
