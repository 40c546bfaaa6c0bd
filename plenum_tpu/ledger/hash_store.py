"""Durable store of Merkle leaf and interior-node hashes.

Reference behavior: ledger/hash_stores/hash_store.py:7 — leaf hashes by
sequence number plus interior hashes, enabling tree recovery on restart and
O(log n) proof generation without rehashing the log.

Layout here: leaves keyed `l<idx>` (0-based), interior nodes keyed by
(level, index) where node (k, i) is the root of leaves [i*2^k, (i+1)*2^k) —
only complete subtrees are stored, which is exactly the set of hashes the
append path computes anyway.
"""
from __future__ import annotations

from typing import Optional

from plenum_tpu.storage.kv_store import KeyValueStorage
from plenum_tpu.storage.kv_memory import KvMemory


class HashStore:
    def __init__(self, kv: Optional[KeyValueStorage] = None):
        self._kv = kv if kv is not None else KvMemory()

    @property
    def kv(self) -> KeyValueStorage:
        """Backing store — exposed so the commit path can group this
        store's rows into the per-3PC-batch atomic write."""
        return self._kv

    @staticmethod
    def _leaf_key(idx: int) -> bytes:
        return b"l" + idx.to_bytes(8, "big")

    @staticmethod
    def _node_key(level: int, idx: int) -> bytes:
        return b"n" + level.to_bytes(1, "big") + idx.to_bytes(8, "big")

    def put_leaf(self, idx: int, digest: bytes) -> None:
        self._kv.put(self._leaf_key(idx), digest)

    def get_leaf(self, idx: int) -> bytes:
        return self._kv.get(self._leaf_key(idx))

    def put_node(self, level: int, idx: int, digest: bytes) -> None:
        self._kv.put(self._node_key(level, idx), digest)

    def get_node(self, level: int, idx: int) -> bytes:
        return self._kv.get(self._node_key(level, idx))

    def try_get_node(self, level: int, idx: int) -> Optional[bytes]:
        return self._kv.try_get(self._node_key(level, idx))

    @property
    def leaf_count(self) -> int:
        # binary search for the first missing leaf
        lo, hi = 0, 1
        while self._kv.has_key(self._leaf_key(hi - 1)):
            lo, hi = hi, hi * 2
        # invariant: leaf lo-1 exists (or lo==0), leaf hi-1 doesn't
        while lo < hi:
            mid = (lo + hi) // 2
            if self._kv.has_key(self._leaf_key(mid)):
                lo = mid + 1
            else:
                hi = mid
        return lo

    def reset(self) -> None:
        self.truncate(0)

    def truncate(self, leaf_count: int) -> None:
        """Drop every leaf at or past `leaf_count` and every interior node
        whose subtree reaches past it: what is left is exactly what
        appending `leaf_count` leaves would have stored."""
        doomed = []
        for k in self._kv.iterator(include_value=False):
            if k[:1] == b"l":
                reach = int.from_bytes(k[1:], "big") + 1
            else:
                reach = (int.from_bytes(k[2:], "big") + 1) << k[1]
            if reach > leaf_count:
                doomed.append(k)
        with self._kv.write_batch():
            for k in doomed:
                self._kv.remove(k)

    def close(self) -> None:
        self._kv.close()


class OverlayHashStore(HashStore):
    """Copy-on-write view over a base store: reads fall through, writes stay in
    memory. Backs the uncommitted shadow tree (3PC staging) so computing an
    uncommitted root never touches durable storage."""

    def __init__(self, base: HashStore):
        super().__init__(KvMemory())
        self._base = base

    def get_leaf(self, idx: int) -> bytes:
        v = self._kv.try_get(self._leaf_key(idx))
        return v if v is not None else self._base.get_leaf(idx)

    def try_get_node(self, level: int, idx: int) -> Optional[bytes]:
        v = self._kv.try_get(self._node_key(level, idx))
        return v if v is not None else self._base.try_get_node(level, idx)

    def get_node(self, level: int, idx: int) -> bytes:
        v = self.try_get_node(level, idx)
        if v is None:
            raise KeyError((level, idx))
        return v

    @property
    def leaf_count(self) -> int:
        raise NotImplementedError("overlay store has no independent leaf count")
