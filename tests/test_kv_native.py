"""Native C++ KV engine: differential-tested against the in-memory model,
plus durability, torn-tail, and compaction behavior.

Reference test model: storage tests for the LevelDB/RocksDB backends.
"""
from __future__ import annotations

import os
import random

import pytest

from plenum_tpu.ledger.compact_merkle_tree import CompactMerkleTree
from plenum_tpu.ledger.hash_store import HashStore
from plenum_tpu.ledger.ledger import Ledger, txn_to_leaf
from plenum_tpu.ledger.merkle_verifier import MerkleVerifier
from plenum_tpu.storage.kv_memory import KvMemory
from plenum_tpu.storage.kv_native import KvNative, native_available

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native toolchain unavailable")


def test_differential_vs_memory_model(tmp_path):
    rng = random.Random(7)
    kv = KvNative(str(tmp_path))
    model = KvMemory()
    keys = [b"k%03d" % i for i in range(50)]
    for _ in range(2000):
        op = rng.randrange(3)
        k = rng.choice(keys)
        if op == 0:
            v = rng.randbytes(rng.randrange(0, 200))
            kv.put(k, v)
            model.put(k, v)
        elif op == 1:
            kv.remove(k)
            model.remove(k)
        else:
            try:
                expect = model.get(k)
            except KeyError:
                with pytest.raises(KeyError):
                    kv.get(k)
            else:
                assert kv.get(k) == expect
    assert list(kv.iterator()) == list(model.iterator())
    assert kv.size == model.size

    # ranged iteration agrees too (inclusive end, KvMemory semantics)
    assert list(kv.iterator(start=b"k010", end=b"k020")) == \
        list(model.iterator(start=b"k010", end=b"k020"))

    # durability: reopen sees the same content
    kv.close()
    kv2 = KvNative(str(tmp_path))
    assert list(kv2.iterator()) == list(model.iterator())
    kv2.close()


def test_torn_tail_drops_only_last_record(tmp_path):
    kv = KvNative(str(tmp_path))
    for i in range(10):
        kv.put(b"key%d" % i, b"value%d" % i)
    # close WITHOUT compaction path interfering: garbage ratio is 0 here
    kv.close()
    path = os.path.join(str(tmp_path), "kv.kvn")
    os.truncate(path, os.path.getsize(path) - 4)
    kv2 = KvNative(str(tmp_path))
    assert kv2.size == 9                 # only the torn record lost
    assert kv2.get(b"key8") == b"value8"
    with pytest.raises(KeyError):
        kv2.get(b"key9")
    # the truncated tail was cut at a record boundary: appends work
    kv2.put(b"key9", b"value9b")
    kv2.close()
    kv3 = KvNative(str(tmp_path))
    assert kv3.get(b"key9") == b"value9b"
    kv3.close()


def test_corrupt_record_detected_by_crc(tmp_path):
    kv = KvNative(str(tmp_path))
    kv.put(b"aa", b"11")
    kv.put(b"bb", b"22")
    kv.close()
    path = os.path.join(str(tmp_path), "kv.kvn")
    data = bytearray(open(path, "rb").read())
    data[-1] ^= 0xFF                     # flip a bit in the LAST record
    open(path, "wb").write(bytes(data))
    kv2 = KvNative(str(tmp_path))
    assert kv2.size == 1                 # corrupt record (and after) dropped
    assert kv2.get(b"aa") == b"11"
    kv2.close()


def test_compaction_shrinks_file_and_preserves_content(tmp_path):
    kv = KvNative(str(tmp_path))
    for round_ in range(20):
        for i in range(20):
            kv.put(b"k%d" % i, b"v%d-%d" % (i, round_))
    path = os.path.join(str(tmp_path), "kv.kvn")
    before = os.path.getsize(path)
    assert kv.garbage_ratio > 0.8
    kv.compact()
    after = os.path.getsize(path)
    assert after < before / 5
    assert kv.size == 20
    assert kv.get(b"k7") == b"v7-19"
    # still writable after compaction
    kv.put(b"new", b"x")
    kv.close()
    kv2 = KvNative(str(tmp_path))
    assert kv2.get(b"new") == b"x" and kv2.size == 21
    kv2.close()


# --- a ledger over the engine: what a committed write reads -------------------


def _native_ledger(path):
    return Ledger(CompactMerkleTree(hash_store=HashStore(
        KvNative(str(path / "hashes")))), KvNative(str(path / "log")))


def _commit_batches(ledger, batches: int, per_batch: int) -> float:
    """Stage and commit as 3PC does -> file reads of the hash store a txn."""
    io = ledger.tree.hash_store.kv.io
    gets = io["gets"]
    for _ in range(batches):
        first = ledger.uncommitted_size
        ledger.append_txns_to_uncommitted(
            [{"n": first + i} for i in range(per_batch)])
        assert len(ledger.commit_txns(per_batch)) == per_batch
    return (io["gets"] - gets) / (batches * per_batch)


def _proofs_hold(ledger, seq_nos) -> None:
    for seq_no in seq_nos:
        info = ledger.merkle_info(seq_no)
        path = ledger.tree.inclusion_proof(seq_no - 1)
        assert info["auditPath"] == [h.hex() for h in path]
        assert info["treeSize"] == ledger.size
        assert MerkleVerifier().verify_inclusion(
            txn_to_leaf(ledger.get_by_seq_no(seq_no)), seq_no - 1,
            ledger.size, path, ledger.root_hash)


def test_committed_write_reads_the_hash_store_less_than_once(tmp_path):
    """On a hash store of 16 384 leaves a committed txn costs under one
    file read (an append's left siblings); with an audit path built per
    committed txn it cost ~12. A proof is built when it is asked for."""
    ledger = _native_ledger(tmp_path)
    ledger.append_batch([{"n": i} for i in range(16384)])
    assert _commit_batches(ledger, 64, 16) < 1.0
    assert ledger.size == 16384 + 1024
    _proofs_hold(ledger, (1, 9000, ledger.size))
    ledger.close()


def test_truncated_ledger_commits_and_proves_as_a_whole_one(tmp_path):
    """The same after Ledger.truncate and a re-append (the restart's
    reconcile path: a tail cut back to the last whole batch, then caught
    up again)."""
    ledger = _native_ledger(tmp_path)
    ledger.append_batch([{"n": i} for i in range(16384 + 40)])
    dropped = ledger.truncate(16384)
    assert [t["n"] for t in dropped] == list(range(16384, 16384 + 40))
    assert _commit_batches(ledger, 64, 16) < 1.0
    assert ledger.size == 16384 + 1024
    _proofs_hold(ledger, (1, 16384, 16385, ledger.size))
    whole = _native_ledger(tmp_path / "whole")
    whole.append_batch([{"n": i} for i in range(16384 + 1024)])
    assert ledger.root_hash == whole.root_hash
    whole.close()
    ledger.close()
