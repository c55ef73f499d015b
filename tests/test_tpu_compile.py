"""Compile every main-path Pallas kernel for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses exactly what the chip's
compiler would (scatters, unsupported gathers, misaligned tiles, VMEM
overruns).  Shapes are the d=768 (GPT-2 small) block's real PCS layout
from ``pcs.shape_for``: its auxiliary-witness commitment.  Each compile
must contain a ``tpu_custom_call``, i.e. the kernel lowered to Mosaic and
did not fall back to its jnp body or to interpret mode.

The largest programs of the block's weight range proof (its 2^27-long
opening sum-check) are compiled too, and must fit in device memory with
next to no temporaries: a whole-array relayout or eq table at that length
once took minutes to compile and 10+ GiB of the chip's 16 GB.

The topology is described inside a fixture (only the worker that runs
this file loads the TPU library), and the persistent compilation cache is
off around the compiles: an executable compiled for an absent chip
cannot be read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import blocks as B
from repro.core import circuit as C
from repro.core import pcs as PCS
from repro.core import pcs as PCS
from repro.core import poseidon2 as P2
from repro.kernels import ops as KOPS
from repro.kernels import sumcheck_round as SR

GPT2_SMALL = B.BlockCfg(family="gpt2", d=768, dff=3072, heads=12,
                        kv_heads=12, dh=64, seq=8)
BLOWUP = 4


def _aux_layout():
    """(rows, cols) of the d=768 block's auxiliary-witness commitment."""
    wb = C.WitnessBuilder("aux")
    B.declare_aux(GPT2_SMALL, wb, None)
    log_r, log_c = PCS.shape_for(wb.pack()[2])
    return 1 << log_r, 1 << log_c


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def tpu_paths(monkeypatch):
    """The ops wrappers as they run on a TPU: kernel path by platform,
    kernels compiled (not interpreted)."""
    monkeypatch.delenv("NANOZK_KERNEL_PATH", raising=False)
    monkeypatch.setattr(KOPS, "on_tpu", lambda: True)


def _u32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _main_path_kernels():
    R, Cn = _aux_layout()
    n_cols = Cn * BLOWUP
    n = R * Cn
    tiles = lambda d: [(1, 4, n // 128, 128)] * d           # noqa: E731
    return {
        # Merkle: column leaves, then node pairs of the first level
        "poseidon2_permute": (KOPS.poseidon2_permute, [(R, P2.WIDTH)]),
        "poseidon2_compress": (KOPS.poseidon2_compress,
                               [(n_cols // 2, P2.DIGEST)] * 2),
        "poseidon2_hash_rows": (KOPS.poseidon2_hash, [(n_cols, R)]),
        # RS encode of the committed rows
        "ntt": (KOPS.ntt, [(R, n_cols)]),
        "ntt_inverse": (lambda x: KOPS.ntt(x, inverse=True),
                        [(R, n_cols)]),
        # fused sum-check round on the opening's (m_lift, e_vec) claim
        "sumcheck_eval_round": (
            lambda *t: SR._eval_round(t, n, interpret=False), tiles(2)),
        "sumcheck_transcript_round": (
            lambda g, s: SR._transcript_round(g, s, interpret=False),
            [(1, 3, 4, 1, 128), (1, P2.WIDTH, 1, 128)]),
        "sumcheck_fold_round": (
            lambda c, *t: SR._fold_round(t, c, n, interpret=False),
            [(1, 4, 1, 128)] + tiles(2)),
        # modmatmul partial evaluations (PCS opening rows, matmul claims)
        "partial_eval_rows_mm": (
            lambda m, e: KOPS._partial_rows_impl(m, e, not KOPS.on_tpu()),
            [(R, Cn), (R, 4)]),
        "partial_eval_cols_mm": (
            lambda m, e: KOPS._partial_cols_impl(m, e, not KOPS.on_tpu()),
            [(R, Cn), (Cn, 4)]),
    }


KERNELS = ("poseidon2_permute", "poseidon2_compress", "poseidon2_hash_rows",
           "ntt", "ntt_inverse", "sumcheck_eval_round",
           "sumcheck_transcript_round", "sumcheck_fold_round",
           "partial_eval_rows_mm", "partial_eval_cols_mm")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, tpu_paths, name):
    fn, shapes = _main_path_kernels()[name]
    compiled = jax.jit(fn).lower(
        *[_u32(one_chip, *s) for s in shapes]).compile()
    assert "tpu_custom_call" in compiled.as_text()


RANGE_N = 1 << 27          # the d=768 weight range proof's opening length


def _range_proof_programs():
    n = RANGE_N
    tiles = [(1, 4, n // 128, 128)] * 2
    i32 = jnp.int32
    return {
        "to_tiles": (SR.to_tiles, [((n, 4), jnp.uint32)], False),
        # the opening's e-vector: one suffix bucket of 4 groups, t = 25
        "bucket_e": (
            lambda e, s, w, i, lo: PCS._bucket_e_impl(e, s, w, i, lo, 25),
            [((n, 4), jnp.uint32), ((4, 2, 25, 4), jnp.uint32),
             ((9, 4), jnp.uint32), ((4, 2), i32), ((4,), i32)], False),
        "sumcheck_eval_round": (
            lambda *t: SR._eval_round(t, n, interpret=False),
            [(s, jnp.uint32) for s in tiles], True),
        "sumcheck_fold_round": (
            lambda c, *t: SR._fold_round(t, c, n, interpret=False),
            [((1, 4, 1, 128), jnp.uint32)]
            + [(s, jnp.uint32) for s in tiles], True),
    }


@pytest.mark.parametrize("name", ["to_tiles", "bucket_e",
                                  "sumcheck_eval_round",
                                  "sumcheck_fold_round"])
def test_range_proof_program_compiles_for_v5e(one_chip, tpu_paths, name):
    fn, args, kernel = _range_proof_programs()[name]
    compiled = jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
        for s, dt in args]).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 26
    assert kernel == ("tpu_custom_call" in compiled.as_text())


def test_kernel_path_default_by_platform(tpu_paths, monkeypatch):
    """On a TPU the prover takes the kernel path unless told otherwise;
    an explicit ``ref`` still selects the jnp oracle."""
    assert KOPS.kernel_path() == "fused"
    monkeypatch.setenv("NANOZK_KERNEL_PATH", "ref")
    assert KOPS.kernel_path() == "ref"
    monkeypatch.setattr(KOPS, "on_tpu", lambda: False)
    monkeypatch.delenv("NANOZK_KERNEL_PATH")
    assert KOPS.kernel_path() == "ref"
