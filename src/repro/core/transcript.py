"""Fiat-Shamir transcript: a duplex Poseidon2 sponge.

The prover and verifier drive identical transcripts; every message that
influences soundness (commitment roots, claimed sums, round polynomials,
evaluation claims) is absorbed before the challenge it gates. Challenges are
field elements read directly from sponge lanes (lanes are uniform in [0, P),
so no rejection sampling is needed); query indices are reduced mod n, whose
per-index total-variation bias (<= n/(4P), tight form r(n-r)/(nP)) is
charged to the soundness budget as the "index_bias" component in
chain.soundness_bound and asserted by repro.analysis.fs_lint.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import field as F
from . import poseidon2 as P2

# Analysis hook (repro.analysis.fs_lint): a recorder object observing every
# transcript event of every live Transcript.  None in production — each hook
# site is a single ``is not None`` test, so the prover pays nothing.  The
# hooks wrap the PUBLIC methods, deliberately ABOVE the jitted _*_impl
# functions: a buggy (or mutated) implementation below still produces
# honest events, which is what lets the lint catch e.g. a squeeze that
# fails to advance the sponge state.
_RECORDER = None


def set_recorder(recorder) -> None:
    """Install (or with None remove) the fs_lint event recorder."""
    global _RECORDER
    _RECORDER = recorder


@functools.partial(jax.jit, static_argnames=("n",))
def _absorb_impl(state: jnp.ndarray, flat: jnp.ndarray, n: int) -> jnp.ndarray:
    state = state.at[P2.RATE].set(F.fadd(state[P2.RATE], F.fconst(n)))
    chunks = flat.reshape(-1, P2.RATE)

    def step(st, chunk):
        st = st.at[:P2.RATE].set(F.fadd(st[:P2.RATE], chunk))
        return P2._permute_impl(st), None
    state, _ = jax.lax.scan(step, state, chunks)
    return state


@functools.partial(jax.jit, static_argnames=("n",))
def _absorb_any(state: jnp.ndarray, elems: jnp.ndarray, n: int) -> jnp.ndarray:
    """Ravel/pad inside the jit so an absorb is ONE host dispatch."""
    flat = jnp.ravel(elems).astype(jnp.uint32)
    pad = (-n) % P2.RATE
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.uint32)])
    return _absorb_impl(state, flat, n)


@functools.partial(jax.jit, static_argnames=("k",))
def _squeeze_impl(state: jnp.ndarray, k: int):
    """Squeeze k lanes: one permutation per RATE lanes, under a scan, so
    the program holds one permutation (unrolled, the 14 permutations of
    a 27-point challenge took 9 s to compile for a TPU v5e; 0.7 s so)."""
    def step(st, _):
        st = P2._permute_impl(st)
        return st, st[:P2.RATE]
    state, out = jax.lax.scan(step, state, None, length=-(-k // P2.RATE))
    return state, out.reshape(-1)[:k]


class Transcript:
    def __init__(self, domain: str):
        self._state = jnp.zeros((P2.WIDTH,), dtype=jnp.uint32)
        if _RECORDER is not None:
            _RECORDER.on_init(self, domain)
        self.absorb(F.f_from_int(np.frombuffer(
            domain.encode()[:32].ljust(32, b"\0"), dtype=np.uint8).astype(np.int64)))

    # -- raw sponge state (used by the fused kernel path) -------------------
    @property
    def state(self) -> jnp.ndarray:
        """Current sponge state, shape (WIDTH,) uint32 Montgomery."""
        return self._state

    def set_state(self, state) -> None:
        """Install a sponge state produced by an equivalent absorb/squeeze
        sequence run elsewhere (e.g. inside a fused kernel)."""
        old = self._state
        self._state = jnp.asarray(state)
        if _RECORDER is not None:
            _RECORDER.on_set_state(self, np.asarray(old),
                                   np.asarray(self._state))

    # -- absorbing ----------------------------------------------------------
    def absorb(self, elems) -> None:
        """Absorb a flat (or any-shape) array of Montgomery field elements.

        Length-bound into the capacity (prefix-free); jitted per shape.
        """
        elems = jnp.asarray(elems)
        n = int(np.prod(elems.shape, dtype=np.int64)) if elems.ndim else 1
        if _RECORDER is not None:
            _RECORDER.on_absorb(self, np.asarray(elems))
        self._state = _absorb_any(self._state, elems, n)

    def absorb_digest(self, digest) -> None:
        self.absorb(digest)

    def absorb_int(self, v: int) -> None:
        self.absorb(F.f_from_int(np.array([v % F.P], np.int64)))

    # -- squeezing ----------------------------------------------------------
    def _squeeze(self, k: int) -> jnp.ndarray:
        old = self._state
        self._state, out = _squeeze_impl(self._state, k)
        if _RECORDER is not None:
            _RECORDER.on_squeeze(self, np.asarray(old),
                                 np.asarray(self._state), np.asarray(out))
        return out

    def challenge_f(self) -> jnp.ndarray:
        """One Fp challenge (Montgomery scalar)."""
        return self._squeeze(1)[0]

    def challenge_f4(self) -> jnp.ndarray:
        """One Fp4 challenge, shape (4,)."""
        return self._squeeze(4)

    def challenge_f4_vec(self, n: int) -> jnp.ndarray:
        """n Fp4 challenges, shape (n, 4)."""
        return self._squeeze(4 * n).reshape(n, 4)

    # Modulo-bias bound for challenge_indices, asserted by fs_lint and
    # charged to the soundness budget (chain.soundness_bound, component
    # "index_bias"): a squeezed lane is uniform on [0, P), so reducing mod
    # n leaves each index distribution within total-variation distance
    #   r * (n - r) / (n * P)  <=  n / (4 * P)          (r = P mod n)
    # of uniform. The soundness accounting folds this per-index bias into
    # the per-query column-miss probability, ((1+rho)/2 + n/(4P))^queries,
    # instead of taking the k-fold union bound (which is vacuously loose
    # at production widths). INDEX_BIAS_PER_CALL reports that union bound
    # k*n/(4P) for one call as a diagnostic; fs_lint asserts the charged
    # per-index term n/(4P) stays below 2^-12 — under 0.02% of the
    # (1+rho)/2 ~ 0.625 factor it perturbs — for every call of a golden
    # prove, which keeps the "index_bias" component negligible.
    INDEX_BIAS_PER_CALL = staticmethod(lambda n, k: k * n / (4 * F.P))

    def challenge_indices(self, n: int, k: int) -> np.ndarray:
        """k query indices in [0, n); per-index TV bias <= n/(4P), see above."""
        raw = F.f_to_int(self._squeeze(k))
        idx = (np.asarray(raw) % n).astype(np.int64)
        if _RECORDER is not None:
            _RECORDER.on_indices(self, n, k, np.asarray(raw), idx)
        return idx
