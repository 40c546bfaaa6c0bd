"""Merkle tree hashing seam — the first of the three crypto provider seams
(SURVEY.md §7 stage 2).

Reference behavior: ledger/tree_hasher.py:4 — RFC-6962 domain separation:
    leaf hash     = SHA256(0x00 || data)
    interior hash = SHA256(0x01 || left || right)
Two backends: `cpu` (hashlib, scalar) and `jax` (batched device kernels from
plenum_tpu.ops.sha256). The batch API is the contract — `hash_leaves` /
`hash_children_batch` take whole vectors so the device backend issues one
dispatch per call, never one per hash.
"""
from __future__ import annotations

import hashlib
from typing import Sequence


class TreeHasher:
    """CPU backend (hashlib)."""

    def hash_empty(self) -> bytes:
        return hashlib.sha256(b"").digest()

    def hash_leaf(self, data: bytes) -> bytes:
        return hashlib.sha256(b"\x00" + data).digest()

    def hash_children(self, left: bytes, right: bytes) -> bytes:
        return hashlib.sha256(b"\x01" + left + right).digest()

    # batch API (scalar loop on CPU; one device call on JAX backend)
    def hash_leaves(self, leaves: Sequence[bytes]) -> list[bytes]:
        return [self.hash_leaf(l) for l in leaves]

    def hash_children_batch(self, pairs: Sequence[tuple[bytes, bytes]]) -> list[bytes]:
        return [self.hash_children(l, r) for l, r in pairs]


def fused_wave_levels(new_hashes, bounds, offs, counts, note_shape=None):
    """One fused device program for an append wave's interior levels
    (ops/sha256.merkle_wave) — the shared implementation behind every
    hasher's `hash_wave_levels`.

    new_hashes: the wave's new level-0 digests (32-byte each).
    bounds[l]:  the old left-boundary digest level l pairs with, or None.
    offs[l]:    1 when level l uses its boundary.
    counts[l]:  how many parents level l really forms (the valid prefix).

    Returns per-level lists of parent digests for the first
    min(len(counts), log2(bucket)) levels; the CALLER finishes any deeper
    (single-node spine) levels on host. note_shape, when given, is called
    with the compiled-shape key so the pipeline's recompile guard can
    count it.
    """
    import jax.numpy as jnp
    import numpy as np

    from plenum_tpu.ops.sha256 import (bytes_to_digests, digests_to_bytes,
                                       merkle_wave)
    n = len(new_hashes)
    bucket = _pow2_at_least(max(2, n))
    depth = bucket.bit_length() - 1          # log2(bucket) program levels
    if note_shape is not None:
        note_shape(("merkle", bucket))
    new0 = np.zeros((bucket, 8), dtype=np.uint32)
    new0[:n] = bytes_to_digests(list(new_hashes))
    bnd = np.zeros((depth, 8), dtype=np.uint32)
    off = np.zeros(depth, dtype=np.int32)
    levels = min(depth, len(counts))
    for l in range(levels):
        if offs[l] and bounds[l] is not None:
            bnd[l] = bytes_to_digests([bounds[l]])[0]
            off[l] = 1
    outs = merkle_wave(jnp.asarray(new0), jnp.asarray(bnd),
                       jnp.asarray(off))
    result = []
    for l in range(levels):
        want = counts[l]
        result.append(digests_to_bytes(np.asarray(outs[l])[:want])
                      if want else [])
    return result


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class JaxTreeHasher(TreeHasher):
    """Device backend: batched SHA-256 (plenum_tpu/ops/sha256.py).

    Scalar calls fall back to hashlib (correctness identical); the wins come
    from the batch entry points used by Ledger.extend_batch and the catchup
    verifier.
    """

    def __init__(self, min_batch: int = 1024, fuse_min: int = None):
        # Below min_batch the dispatch overhead beats the VPU win — hashlib
        # does 1024 sha256 in under a millisecond while a device dispatch
        # has a fixed launch + transfer cost, so only catchup-scale batch
        # verification and bulk appends go to the device (the threshold's
        # value is re-derived on the attached chip under ROADMAP C10).
        self._min_batch = min_batch
        # fused append waves pay ONE dispatch for all interior levels, so
        # they amortize earlier than the flat batch threshold
        self._fuse_min = min_batch if fuse_min is None else fuse_min

    def hash_wave_levels(self, new_hashes, bounds, offs, counts):
        """Fused interior levels for one append wave, or None to decline
        (small waves stay on the hashlib per-level path)."""
        if len(new_hashes) < self._fuse_min:
            return None
        return fused_wave_levels(new_hashes, bounds, offs, counts)

    def hash_leaves(self, leaves: Sequence[bytes]) -> list[bytes]:
        if len(leaves) < self._min_batch:
            return [self.hash_leaf(l) for l in leaves]
        from plenum_tpu.ops.sha256 import sha256_batch
        return sha256_batch(list(leaves), prefix=b"\x00")

    def hash_children_batch(self, pairs: Sequence[tuple[bytes, bytes]]) -> list[bytes]:
        if len(pairs) < self._min_batch:
            return [self.hash_children(l, r) for l, r in pairs]
        import jax.numpy as jnp
        from plenum_tpu.ops.sha256 import (hash_interior, bytes_to_digests,
                                           digests_to_bytes)
        n = len(pairs)
        n_pad = 1
        while n_pad < n:
            n_pad *= 2
        lefts = bytes_to_digests([p[0] for p in pairs] + [b"\x00" * 32] * (n_pad - n))
        rights = bytes_to_digests([p[1] for p in pairs] + [b"\x00" * 32] * (n_pad - n))
        out = digests_to_bytes(hash_interior(jnp.asarray(lefts), jnp.asarray(rights)))
        return out[:n]


def make_tree_hasher(backend: str) -> TreeHasher:
    if backend in ("jax", "jax-sharded"):
        return JaxTreeHasher()
    return TreeHasher()
