"""Merkle trees over Poseidon2 digests (vector commitments for the PCS).

Leaves are rows of field elements (Montgomery uint32). The tree is built
level-by-level with the vectorized 2-to-1 compression, so committing is one
batched sponge pass plus log2(n) batched compressions — entirely jnp.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np
import jax
import jax.numpy as jnp

from . import poseidon2 as P2

from repro.kernels import ahead as AH
from repro.kernels import ops as KOPS
from repro.kernels import poseidon2_kernel as PK


def _hash_leaves(leaves: jnp.ndarray) -> jnp.ndarray:
    """Leaf sponge pass, kernel-batched on the fused path (bit-identical to
    P2.hash_elems — same length tag, chunk schedule and permutation)."""
    if KOPS.use_fused():
        return KOPS.poseidon2_hash(leaves)
    return P2.hash_elems(leaves)


def _compress_level(left: jnp.ndarray, right: jnp.ndarray) -> jnp.ndarray:
    """2-to-1 level compression, kernel-batched on the fused path."""
    if KOPS.use_fused():
        return KOPS.poseidon2_compress(left, right)
    return P2.compress(left, right)


def prepare(shape) -> list:
    """Start compiling, on a TPU's kernel path, the leaf hash and every
    level's compression of a tree over leaves of ``shape`` ((n, leaf_len)
    or (B, n, leaf_len)); their futures in the order ``commit`` runs
    them, or none elsewhere."""
    if not (KOPS.use_fused() and KOPS.on_tpu()):
        return []
    *batch, n, _ = shape

    def u32(*s):
        return jax.ShapeDtypeStruct((*batch, *s), jnp.uint32)

    futs = [AH.start(PK.hash_rows, u32(*shape[-2:]), interpret=False)]
    k = 1 << max((n - 1).bit_length(), 0) if n > 1 else 1
    while k > 1:
        k //= 2
        futs.append(AH.start(PK.compress_pairs, u32(k, P2.DIGEST),
                             u32(k, P2.DIGEST), interpret=False))
    return futs


def _wait(futs: list, i: int) -> None:
    if futs:
        AH.wait(futs[i])


@dataclasses.dataclass
class MerkleTree:
    levels: List[jnp.ndarray]  # levels[0]: (n, DIGEST) leaf digests ... root last

    @property
    def root(self) -> jnp.ndarray:
        return self.levels[-1][0]

    @property
    def num_leaves(self) -> int:
        return self.levels[0].shape[0]


def commit(leaves: jnp.ndarray) -> MerkleTree:
    """leaves: (n, leaf_len) field elements; n padded to a power of two."""
    n = leaves.shape[0]
    futs = prepare(leaves.shape)
    _wait(futs, 0)
    digests = _hash_leaves(leaves)
    n_pad = 1 << max((n - 1).bit_length(), 0) if n > 1 else 1
    if n_pad != n:
        digests = jnp.concatenate(
            [digests, jnp.zeros((n_pad - n, P2.DIGEST), dtype=jnp.uint32)], axis=0)
    levels = [digests]
    while levels[-1].shape[0] > 1:
        cur = levels[-1]
        _wait(futs, len(levels))
        levels.append(_compress_level(cur[0::2], cur[1::2]))
    return MerkleTree(levels=levels)


def commit_batch(leaves: jnp.ndarray) -> List[MerkleTree]:
    """Commit B same-shape leaf sets at once: leaves (B, n, leaf_len).

    One sponge pass hashes all B*n leaves and each tree level is one batched
    compression over the whole group, so committing L+1 boundary activations
    costs the same number of kernel dispatches as committing one.  Poseidon2
    is elementwise over leading axes, so every returned tree (and root) is
    bit-identical to ``commit(leaves[i])``.
    """
    b, n = leaves.shape[0], leaves.shape[1]
    futs = prepare(leaves.shape)
    _wait(futs, 0)
    digests = _hash_leaves(leaves)                        # (B, n, DIGEST)
    n_pad = 1 << max((n - 1).bit_length(), 0) if n > 1 else 1
    if n_pad != n:
        digests = jnp.concatenate(
            [digests,
             jnp.zeros((b, n_pad - n, P2.DIGEST), dtype=jnp.uint32)], axis=1)
    levels = [digests]
    while levels[-1].shape[1] > 1:
        cur = levels[-1]
        _wait(futs, len(levels))
        levels.append(_compress_level(cur[:, 0::2], cur[:, 1::2]))
    return [MerkleTree(levels=[lv[i] for lv in levels]) for i in range(b)]


@dataclasses.dataclass
class MerklePath:
    index: int
    siblings: np.ndarray  # (depth, DIGEST) uint32 (Montgomery), host-side


def open_path(tree: MerkleTree, index: int) -> MerklePath:
    sibs = []
    idx = index
    for level in tree.levels[:-1]:
        sibs.append(np.asarray(level[idx ^ 1]))
        idx >>= 1
    return MerklePath(index=index, siblings=np.stack(sibs) if sibs else
                      np.zeros((0, P2.DIGEST), np.uint32))


def verify_path(root: np.ndarray, leaf: jnp.ndarray, path: MerklePath) -> bool:
    """Recompute root from a leaf row and its authentication path."""
    node = P2.hash_elems(jnp.asarray(leaf))
    idx = path.index
    for sib in path.siblings:
        sib = jnp.asarray(sib)
        if idx & 1:
            node = P2.compress(sib, node)
        else:
            node = P2.compress(node, sib)
        idx >>= 1
    return bool(np.array_equal(np.asarray(node), np.asarray(root)))


def batch_open(tree: MerkleTree, indices) -> List[MerklePath]:
    # one host copy per level, not one device gather per (index, level)
    host = MerkleTree(levels=[np.asarray(lv) for lv in tree.levels])
    return [open_path(host, int(i)) for i in indices]


def root_from_path(leaf: jnp.ndarray, path: MerklePath) -> np.ndarray:
    """Recompute the root implied by a leaf + path (no comparison)."""
    node = P2.hash_elems(jnp.asarray(leaf))
    idx = path.index
    for sib in path.siblings:
        sib = jnp.asarray(sib)
        node = P2.compress(sib, node) if idx & 1 else P2.compress(node, sib)
        idx >>= 1
    return np.asarray(node)


# ---------------------------------------------------------------------------
# Multiproofs: one deduplicated authentication structure for a set of
# leaves of one tree.  Shared path prefixes between the leaves are shipped
# exactly once — the node list contains, level by level (leaf level first)
# and position-ascending within each level, precisely those sibling digests
# that the verifier cannot derive from the leaves themselves.  This is the
# wire form behind ColumnStore: per Merkle root, per attestation, each
# internal node travels at most once.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class MerkleMultiProof:
    indices: np.ndarray   # (k,) int64, sorted unique leaf positions
    leaves: np.ndarray    # (k, leaf_len) uint32 leaf rows (the columns)
    nodes: np.ndarray     # (n_nodes, DIGEST) uint32, canonical order
    depth: int            # tree depth (2^depth leaves)


def _multiproof_node_positions(indices: np.ndarray, depth: int):
    """Canonical (level, position) list of non-derivable sibling nodes."""
    known = sorted({int(i) for i in indices})
    needed = []
    for _d in range(depth):
        kset = set(known)
        level_needed = sorted({p ^ 1 for p in kset} - kset)
        needed.append(level_needed)
        known = sorted({p >> 1 for p in kset})
    return needed


def build_multiproof(tree: MerkleTree, all_leaves: jnp.ndarray,
                     indices) -> MerkleMultiProof:
    """Open a set of leaf positions with shared prefixes deduplicated.

    all_leaves: the full (n, leaf_len) leaf matrix the tree was built over.
    """
    idx = np.array(sorted({int(i) for i in indices}), dtype=np.int64)
    depth = len(tree.levels) - 1
    nodes = []
    for d, level_needed in enumerate(_multiproof_node_positions(idx, depth)):
        lvl = np.asarray(tree.levels[d])
        for p in level_needed:
            nodes.append(lvl[p])
    leaves = np.asarray(all_leaves)[idx].astype(np.uint32)
    return MerkleMultiProof(
        indices=idx, leaves=leaves,
        nodes=np.stack(nodes) if nodes else np.zeros((0, P2.DIGEST),
                                                     np.uint32),
        depth=depth)


def multiproof_from_paths(indices, leaf_rows: np.ndarray,
                          paths: List[MerklePath], depth: int
                          ) -> MerkleMultiProof:
    """Rebuild the deduplicated multiproof from per-leaf paths (used when
    re-encoding a v1 attestation to v2 without access to the tree)."""
    order = np.argsort(np.asarray(indices, dtype=np.int64), kind="stable")
    seen = {}
    for o in order:
        i = int(indices[o])
        if i not in seen:
            seen[i] = (np.asarray(leaf_rows[o]), paths[o])
    idx = np.array(sorted(seen), dtype=np.int64)
    leaves = np.stack([seen[i][0] for i in idx]) if len(idx) else \
        np.zeros((0, 0), np.uint32)
    # sibling value at (level d, position s) comes from any path of a leaf
    # j with (j >> d) == s ^ 1
    by_level: List[dict] = [{} for _ in range(depth)]
    for i in idx:
        _, path = seen[int(i)]
        assert path.siblings.shape[0] == depth, "path depth mismatch"
        for d in range(depth):
            by_level[d][(int(i) >> d) ^ 1] = path.siblings[d]
    nodes = []
    for d, level_needed in enumerate(
            _multiproof_node_positions(idx, depth)):
        for p in level_needed:
            nodes.append(np.asarray(by_level[d][p]))
    return MerkleMultiProof(
        indices=idx, leaves=leaves.astype(np.uint32),
        nodes=np.stack(nodes) if nodes else np.zeros((0, P2.DIGEST),
                                                     np.uint32),
        depth=depth)


def verify_multiproof(root: np.ndarray, mp: MerkleMultiProof) -> bool:
    """Recompute the root from a multiproof; every node must be consumed."""
    if not isinstance(mp, MerkleMultiProof):
        return False
    idx = np.asarray(mp.indices)
    nodes = np.asarray(mp.nodes)
    leaves = np.asarray(mp.leaves)
    if (idx.ndim != 1 or not np.issubdtype(idx.dtype, np.integer)
            or leaves.ndim != 2 or leaves.shape[0] != idx.shape[0]
            or nodes.ndim != 2 or nodes.shape[1:] != (P2.DIGEST,)
            or not isinstance(mp.depth, int) or mp.depth < 0
            or mp.depth > 40):
        return False
    if idx.shape[0] == 0:
        return False
    if idx.min() < 0 or idx.max() >= (1 << mp.depth):
        return False
    if np.any(np.diff(idx) <= 0):        # sorted + unique is canonical
        return False
    digests = {int(i): P2.hash_elems(jnp.asarray(leaves[k]))
               for k, i in enumerate(idx)}
    cursor = 0
    for _d in range(mp.depth):
        kset = set(digests)
        level_needed = sorted({p ^ 1 for p in kset} - kset)
        for p in level_needed:
            if cursor >= nodes.shape[0]:
                return False
            digests[p] = jnp.asarray(nodes[cursor])
            cursor += 1
        nxt = {}
        for p in sorted({q >> 1 for q in kset}):
            nxt[p] = P2.compress(digests[2 * p], digests[2 * p + 1])
        digests = nxt
    if cursor != nodes.shape[0]:         # extra nodes = non-canonical proof
        return False
    return bool(np.array_equal(np.asarray(digests[0]), np.asarray(root)))


def verify_paths_batch(root: np.ndarray, leaves: jnp.ndarray,
                       paths: List[MerklePath]) -> bool:
    """Verify many authentication paths with one compress per level
    (vectorized over queries — the verifier's hot loop)."""
    t = len(paths)
    if t == 0:
        return True
    depth = paths[0].siblings.shape[0]
    if any(p.siblings.shape[0] != depth for p in paths):
        return False
    idx = np.array([p.index for p in paths], dtype=np.int64)
    sibs = jnp.asarray(np.stack([p.siblings for p in paths]))  # (t, d, 8)
    node = P2.hash_elems(jnp.asarray(leaves))                  # (t, 8)
    for d in range(depth):
        bit = jnp.asarray((idx >> d) & 1, dtype=jnp.uint32)[:, None]
        sib = sibs[:, d]
        left = jnp.where(bit.astype(bool), sib, node)
        right = jnp.where(bit.astype(bool), node, sib)
        node = P2.compress(left, right)
    root_b = jnp.broadcast_to(jnp.asarray(root), node.shape)
    return bool(np.array_equal(np.asarray(node), np.asarray(root_b)))
