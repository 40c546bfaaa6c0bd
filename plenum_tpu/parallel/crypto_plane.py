"""Sharded crypto batch plane: the multi-chip "training step" of the framework.

Reference behavior being replaced (SURVEY.md §3.2 hot spots): per-message
scalar Ed25519 verification on every node (client_authn.py:273 via
nacl_wrappers.py:62) and scalar SHA-256 Merkle appends (ledger/tree_hasher.py).
Here one SPMD program verifies an [inst, n_sigs] grid of signatures and
reduces a Merkle root over [n_leaves] leaf digests, sharded over a 2-D
("inst", "sig") mesh (plenum_tpu/parallel/mesh.py).

Sharding layout (scaling-book recipe: pick mesh, annotate, let XLA insert
collectives — here the cross-shard reduce is explicit via shard_map):
  - signature tensors: batch axes sharded over ("inst", "sig"); the 254-round
    double-scalar-mult advances all lanes in lockstep, zero communication.
  - Merkle leaves: sharded over the flattened mesh; each shard reduces its
    local complete subtree, then all_gathers the per-shard roots (one small
    [n_shards, 8]-word collective on ICI) and finishes the top of the tree
    redundantly on every device.
  - verdict count: a psum — the protocol only needs "how many bad" to decide
    whether to walk the verdict vector on host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
from plenum_tpu.ops import ed25519 as ed_ops
from plenum_tpu.ops import sha256 as sha_ops


def _reduce_roots(roots: jax.Array) -> jax.Array:
    """Top of the Merkle tree over per-shard roots; pads a non-power-of-two
    shard count by repeating the last root (shapes are static so this is
    resolved at trace time)."""
    s = roots.shape[0]
    p = 1
    while p < s:
        p *= 2
    if p != s:
        roots = jnp.concatenate(
            [roots, jnp.broadcast_to(roots[-1:], (p - s, 8))], axis=0)
    return sha_ops.merkle_reduce_pow2(roots)


def _local_step_bytes(s_u8, h_u8, keys_u8, idx, r_u8, leaves):
    """Per-shard body of the COMPRESSED dispatch (the production path):
    raw byte payloads arrive sharded over the grid, the 32 B/key verkey
    table is REPLICATED (it IS the deduped payload — the transfer win
    must survive sharding), and each shard decompresses the keys it
    needs on device. Key decompression is redundant across shards by
    design: ~0.5 signature-equivalents of compute per distinct key vs
    an all-to-all of 1280 B/key quarter-point rows."""
    i_loc, n_loc = idx.shape[0], idx.shape[1]
    m = i_loc * n_loc
    ok = ed_ops.verify_kernel_bytes(
        s_u8.reshape(m, 32), h_u8.reshape(m, 32), keys_u8,
        idx.reshape(m), r_u8.reshape(m, 32))
    ok = ok.reshape(i_loc, n_loc)

    local_root = sha_ops.merkle_reduce_pow2(leaves)               # [8]
    roots = jax.lax.all_gather(local_root, ("inst", "sig"))       # [S, 8]
    root = _reduce_roots(roots)                                   # [8]

    n_ok = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), ("inst", "sig"))
    return ok, root, n_ok


def _local_step(s_dig, h_dig, aq_unique, idx, ry, r_sign, leaves):
    """Per-shard body. Signature grid arrives as [I_loc, N_loc, ...]; the
    local grid flattens into one kernel batch. The verkey quarter-point
    table is REPLICATED (it is the deduped host->device payload — the
    transfer win must survive sharding) and gathered per shard by the
    sharded idx. leaves: uint32[L_loc, 8]."""
    i_loc, n_loc = idx.shape[0], idx.shape[1]
    m = i_loc * n_loc
    aq = jnp.take(aq_unique, idx.reshape(m), axis=0)
    ok = ed_ops.verify_kernel(
        s_dig.reshape(ed_ops.N_COMB, m),
        h_dig.reshape(ed_ops.N_WIN, ed_ops.N_QUARTERS, m),
        aq,
        ry.reshape(m, -1), r_sign.reshape(m))
    ok = ok.reshape(i_loc, n_loc)

    local_root = sha_ops.merkle_reduce_pow2(leaves)               # [8]
    roots = jax.lax.all_gather(local_root, ("inst", "sig"))       # [S, 8]
    root = _reduce_roots(roots)                                   # [8]

    n_ok = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), ("inst", "sig"))
    return ok, root, n_ok


class ShardedCryptoPlane:
    """One-dispatch-per-prod-cycle crypto plane over a device mesh.

    verify+merkle+count in a single compiled SPMD program; the host-side
    consensus engine stages batches in, reads verdict vectors out
    (SURVEY.md §7 stage 6 "accumulate-then-flush batch queues").
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        spec_s = P(None, "inst", "sig")            # s digits [N_COMB, I, N]
        spec_h = P(None, None, "inst", "sig")      # h digits [W, 4, I, N]
        spec_aq = P(None, None, None, None)        # aq_unique [U, 4, 4, L]
        spec_idx = P("inst", "sig")                # idx      [I, N]
        spec_ry = P("inst", "sig", None)           # ry       [I, N, L]
        spec_scalar = P("inst", "sig")             # r_sign   [I, N]
        spec_leaf = P(("inst", "sig"), None)       # leaves   [L, 8]
        # check_vma off: verify_kernel seeds its fori_loop carry with
        # device-invariant constants (the identity point), which the varying-
        # manual-axes checker flags even though the computation is replicated-
        # safe.
        self._step = jax.jit(jax.shard_map(
            _local_step, mesh=mesh,
            in_specs=(spec_s, spec_h, spec_aq, spec_idx, spec_ry,
                      spec_scalar, spec_leaf),
            out_specs=(P("inst", "sig"), P(), P()),
            check_vma=False))
        spec_bytes = P("inst", "sig", None)       # u8 payloads [I, N, 32]
        self._step_bytes = jax.jit(jax.shard_map(
            _local_step_bytes, mesh=mesh,
            in_specs=(spec_bytes, spec_bytes, P(None, None), spec_idx,
                      spec_bytes, spec_leaf),
            out_specs=(P("inst", "sig"), P(), P()),
            check_vma=False))

    def step(self, s_dig, h_dig, aq_unique, idx, ry, r_sign, leaves):
        """-> (ok[I, N] bool, root uint32[8], n_ok int32).

        Shape contract: idx is [I, N] with I dividing mesh 'inst' exactly
        and N dividing 'sig'; aq_unique [U, 4, 4, L] is replicated; the
        leaf count divides the full mesh and the per-shard leaf count is a
        power of two (host pads; padding is duplicate leaves whose root
        the host discards if it padded).
        """
        return self._step(s_dig, h_dig, aq_unique, idx, ry, r_sign, leaves)

    def step_bytes(self, s_u8, h_u8, keys_u8, idx, r_u8, leaves):
        """Compressed-dispatch twin of `step` (the production path):
        -> (ok[I, N] bool, root uint32[8], n_ok int32). Byte payloads
        [I, N, 32] shard over the grid; keys_u8 [U, 32] is replicated."""
        return self._step_bytes(s_u8, h_u8, keys_u8, idx, r_u8, leaves)


class ShardedJaxEd25519Verifier(JaxEd25519Verifier):
    """JaxEd25519Verifier whose device program is the SPMD crypto plane:
    identical host staging (decompression cache, scalar windows, padding),
    but the dispatch shards the signature grid over the plane's mesh, so
    every pool node's traffic runs as a multi-chip program. This is the
    production seam for `crypto_backend="jax-sharded"`: node traffic
    flows through `ShardedCryptoPlane.step` (SURVEY.md §2.3
    distributed-comm row)."""

    def __init__(self, plane: ShardedCryptoPlane, min_batch: int = 1,
                 cache_size: int = 65536):
        inst = plane.mesh.shape["inst"]
        sig = plane.mesh.shape["sig"]
        if inst & (inst - 1) or sig & (sig - 1):
            raise ValueError(
                f"mesh axes must be powers of two for the pow2-padded "
                f"dispatch to tile exactly, got inst={inst} sig={sig}")
        # every dispatch must fill the grid: at least one lane per shard
        super().__init__(min_batch=max(min_batch, inst * sig),
                         cache_size=cache_size)
        self._plane = plane
        self._grid = (inst, sig)
        self.dispatches = 0          # observability for tests/metrics
        self.rewarms = 0

    def rewarm(self) -> None:
        """Plane-supervisor re-warm hook: drop the staged quarter-point
        key rows so the next dispatch re-uploads the replicated verkey
        table to every shard (after a mesh/runtime restart the device-side
        copies are gone; the compiled SPMD program itself persists in the
        XLA cache, and the supervisor's probe batch re-validates it at a
        compiled shape before traffic is re-admitted)."""
        super().rewarm()
        self.rewarms += 1

    def _device_verify_bytes(self, s_u8, h_u8, k_u8, idx, r_u8):
        """The compressed staging reshaped onto the plane's grid; the
        unique-key byte table rides replicated (32 B/key, the whole
        point of the dispatch)."""
        import jax.numpy as jnp
        inst, sig = self._grid
        m = s_u8.shape[0]
        n = m // inst
        leaves = jnp.zeros((inst * sig, 8), jnp.uint32)
        ok, _root, _n_ok = self._plane.step_bytes(
            jnp.asarray(s_u8).reshape(inst, n, 32),
            jnp.asarray(h_u8).reshape(inst, n, 32),
            jnp.asarray(k_u8),
            jnp.asarray(idx).reshape(inst, n),
            jnp.asarray(r_u8).reshape(inst, n, 32),
            leaves)
        self.dispatches += 1
        return ok.reshape(m)

    def _device_verify(self, s_digits, h_digits, aq_unique, idx, ry, r_sign):
        import jax.numpy as jnp
        inst, sig = self._grid
        m = s_digits.shape[1]        # pow2 >= inst*sig, so inst | m and
        n = m // inst                # sig | n: the grid tiles exactly
        # the plane fuses a Merkle reduction; this path only needs verdicts,
        # so feed one zero leaf per shard and drop the root
        leaves = jnp.zeros((inst * sig, 8), jnp.uint32)
        ok, _root, _n_ok = self._plane.step(
            jnp.asarray(s_digits).reshape(ed_ops.N_COMB, inst, n),
            jnp.asarray(h_digits).reshape(
                ed_ops.N_WIN, ed_ops.N_QUARTERS, inst, n),
            jnp.asarray(aq_unique),
            jnp.asarray(idx).reshape(inst, n),
            jnp.asarray(ry).reshape(inst, n, -1),
            jnp.asarray(r_sign).reshape(inst, n),
            leaves)
        self.dispatches += 1
        return ok.reshape(m)


def make_sharded_verifier(min_batch: int = 1,
                          n_devices=None) -> ShardedJaxEd25519Verifier:
    """Plane + verifier over the local devices. The dispatch tiles pow2
    batches, so a non-pow2 device count (e.g. 6) is trimmed to its largest
    pow2 subset rather than failing construction."""
    import jax

    from .mesh import make_mesh
    avail = len(jax.devices()) if n_devices is None else n_devices
    pow2 = 1
    while pow2 * 2 <= avail:
        pow2 *= 2
    plane = ShardedCryptoPlane(make_mesh(pow2))
    return ShardedJaxEd25519Verifier(plane, min_batch=min_batch)
