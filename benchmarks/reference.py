"""The plain reference: a sequential executor made of hashlib and dicts.

It reads a domain ledger's transactions in order, recomputes the RFC 6962
Merkle root over their leaves, and replays them into DID -> verkey and
(DID, attribute) -> value. It imports nothing of plenum_tpu: the leaf
encoding (msgpack of the transaction with map keys sorted) is restated
here, so a change of encoding in the program shows as a root mismatch."""
from __future__ import annotations

import hashlib
import json

import msgpack

NYM, ATTRIB = "1", "100"


def _sorted(obj):
    if isinstance(obj, dict):
        return {k: _sorted(obj[k]) for k in sorted(obj)}
    if isinstance(obj, (list, tuple)):
        return [_sorted(v) for v in obj]
    return obj


def leaf_bytes(txn: dict) -> bytes:
    return msgpack.packb(_sorted(txn), use_bin_type=True)


def merkle_root(leaves) -> bytes:
    """RFC 6962 section 2.1: leaf hash SHA-256(0x00 || leaf), interior
    SHA-256(0x01 || left || right), split at the largest power of two
    smaller than n. Iterative over a stack of (height, hash)."""
    stack: list[tuple[int, bytes]] = []
    count = 0
    for leaf in leaves:
        node = (0, hashlib.sha256(b"\x00" + leaf).digest())
        while stack and stack[-1][0] == node[0]:
            height, left = stack.pop()
            node = (height + 1,
                    hashlib.sha256(b"\x01" + left + node[1]).digest())
        stack.append(node)
        count += 1
    if not count:
        return hashlib.sha256(b"").digest()
    _, root = stack.pop()
    while stack:
        _, left = stack.pop()
        root = hashlib.sha256(b"\x01" + left + root).digest()
    return root


class Replay:
    """DID -> verkey and (DID, attribute name) -> raw value, as the
    ledger's transactions define them."""

    def __init__(self):
        self.verkeys: dict[str, str] = {}
        self.attrs: dict[tuple[str, str], str] = {}
        self.size = 0

    def apply(self, txn: dict) -> None:
        body = txn["txn"]
        data = body["data"]
        if body["type"] == NYM:
            if "verkey" in data or data["dest"] not in self.verkeys:
                self.verkeys[data["dest"]] = data.get("verkey")
        elif body["type"] == ATTRIB and "raw" in data:
            for name in json.loads(data["raw"]):
                self.attrs[(data["dest"], name)] = data["raw"]
        self.size += 1


def replay(txns) -> tuple[bytes, Replay]:
    """-> (Merkle root over the transactions in order, the replayed state)."""
    state = Replay()
    leaves = []
    for txn in txns:
        state.apply(txn)
        leaves.append(leaf_bytes(txn))
    return merkle_root(leaves), state
