"""A pool on durable stores after a crash of ALL its validators.

What a SIGKILL in mid-commit leaves (docs/durability.md) and what a start
does about it: one validator's stores reconciled to the last batch all of
them hold (node/bootstrap.py), the validators of one pool found at
different batches converging without a view change (catchup/cons_proof.py
rejoin, node.py), proofs served from the recovered BLS store before any
new batch (reads/plane.py restore_anchors, MessageReq MULTI_SIG). Each test
fails without the repair it names.
"""
from __future__ import annotations

import os
import shutil

import pytest

from plenum_tpu.common.node_messages import AUDIT_LEDGER_ID, DOMAIN_LEDGER_ID
from plenum_tpu.common.request import Request
from plenum_tpu.config import Config
from plenum_tpu.crypto.ed25519 import Ed25519Signer
from plenum_tpu.execution.txn import GET_NYM
from plenum_tpu.ledger.compact_merkle_tree import CompactMerkleTree
from plenum_tpu.ledger.hash_store import HashStore
from plenum_tpu.ledger.ledger import Ledger
from plenum_tpu.node.bootstrap import last_whole_batch
from plenum_tpu.storage.kv_native import KvNative, native_available

from test_pool import Pool, signed_nym

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native kvstore engine unavailable")


def _user(tag: str) -> Ed25519Signer:
    return Ed25519Signer(seed=tag.encode().ljust(32, b"\0"))


def _pool(tmp_path) -> Pool:
    return Pool(config=Config(Max3PCBatchWait=0.05, kv_backend="native"),
                data_dir=str(tmp_path))


def _order(pool: Pool, tag: str, req_id: int) -> None:
    pool.submit(signed_nym(pool.trustee, _user(tag), req_id))
    pool.run(5.0)


def _crash_all(pool: Pool) -> None:
    """SIGKILL to every validator. The sim keeps a dropped node's timers
    running on the shared clock, so the dead are also silenced: a killed
    process sends nothing."""
    for name in list(pool.nodes):
        dead = pool.nodes[name]
        pool.crash_node(name)
        dead.node_bus.send = lambda *a, **k: None


def _restart_all(pool: Pool, seconds: float = 20.0) -> None:
    """What tools/start_node.py does on a data directory that holds
    ledgers: build, connect, rejoin."""
    for name in pool.names:
        pool.start_node(name)
    pool.net.connect_all()
    for node in pool.nodes.values():
        node.rejoin_after_restart()
    pool.run(seconds)


def _views(pool: Pool) -> set:
    out = set()
    for node in pool.nodes.values():
        db = node.c.db
        dom, aud = db.get_ledger(DOMAIN_LEDGER_ID), db.get_ledger(
            AUDIT_LEDGER_ID)
        out.add((dom.size, dom.root_hash, aud.size, aud.root_hash,
                 db.get_state(DOMAIN_LEDGER_ID).committed_head_hash))
    return out


def _snapshot(tmp_path, names, to: str) -> None:
    for name in names:
        shutil.copytree(os.path.join(str(tmp_path), name),
                        os.path.join(str(tmp_path), to, name))


def _restore(tmp_path, names, frm: str) -> None:
    for name in names:
        shutil.rmtree(os.path.join(str(tmp_path), name))
        shutil.copytree(os.path.join(str(tmp_path), frm, name),
                        os.path.join(str(tmp_path), name))


def _pool_at_two_batches(tmp_path, behind):
    """All four crashed; `behind` hold batches 1-2, the others 1-3."""
    pool = _pool(tmp_path)
    _order(pool, "dr-1", 1)
    _order(pool, "dr-2", 2)
    _crash_all(pool)
    _snapshot(tmp_path, behind, "at2")
    _restart_all(pool, 5.0)
    _order(pool, "dr-3", 3)
    assert {v[0] for v in _views(pool)} == {4}
    _crash_all(pool)
    _restore(tmp_path, behind, "at2")
    return pool


# --- one store, one ledger ---------------------------------------------------


def test_hash_store_with_a_prefix_of_a_scope_still_appends(tmp_path):
    """The engine can flush a scope's leaf rows and not the interior nodes
    over them. Appending to such a tree needs the old left sibling at some
    level: recomputed from below (compact_merkle_tree._level_hash) where
    the store lacks it, instead of a KeyError at the next commit."""
    whole = Ledger(CompactMerkleTree(hash_store=HashStore(
        KvNative(str(tmp_path / "whole_h")))), KvNative(str(tmp_path / "w")))
    for i in range(7):
        whole.append({"n": i})
    store = HashStore(KvNative(str(tmp_path / "torn_h")))
    torn = Ledger(CompactMerkleTree(hash_store=store),
                  KvNative(str(tmp_path / "t")))
    for i in range(6):
        torn.append({"n": i})
    for key in [k for k in store.kv.iterator(include_value=False)
                if k[:1] == b"n"]:
        store.kv.remove(key)            # every interior node lost
    again = Ledger(CompactMerkleTree(hash_store=store), torn.txn_log)
    assert again.size == 6
    again.append({"n": 6})
    assert again.root_hash == whole.root_hash
    assert again.merkle_info(3)["auditPath"] == whole.merkle_info(
        3)["auditPath"]


def test_ledger_truncate_leaves_what_appending_would(tmp_path):
    short = Ledger(CompactMerkleTree(hash_store=HashStore(
        KvNative(str(tmp_path / "sh")))), KvNative(str(tmp_path / "sl")))
    long = Ledger(CompactMerkleTree(hash_store=HashStore(
        KvNative(str(tmp_path / "lh")))), KvNative(str(tmp_path / "ll")))
    for i in range(11):
        long.append({"n": i})
        if i < 5:
            short.append({"n": i})
    dropped = long.truncate(5)
    assert [t["n"] for t in dropped] == list(range(5, 11))
    assert (long.size, long.root_hash) == (5, short.root_hash)
    assert sorted(long.tree.hash_store.kv.iterator(include_value=False)) \
        == sorted(short.tree.hash_store.kv.iterator(include_value=False))
    long.append({"n": 5})
    short.append({"n": 5})
    assert long.root_hash == short.root_hash


def test_hash_store_ahead_of_the_log_is_cut_not_rebuilt(tmp_path):
    """A crash that flushed the hash store's rows and not the log's."""
    ledger = Ledger(CompactMerkleTree(hash_store=HashStore(
        KvNative(str(tmp_path / "h")))), KvNative(str(tmp_path / "l")))
    for i in range(9):
        ledger.append({"n": i})
    root_at_6 = ledger.tree.merkle_tree_hash(0, 6)
    for seq in (7, 8, 9):
        ledger.txn_log.remove(seq)
    again = Ledger(CompactMerkleTree(hash_store=ledger.tree.hash_store),
                   ledger.txn_log)
    assert (again.size, again.root_hash) == (6, root_at_6)
    assert again.tree.hash_store.leaf_count == 6


# --- one validator's stores ---------------------------------------------------


def _record_ends(path: str) -> list[int]:
    """Where each record of a native store's file ends (kvstore.cpp:
    u32 crc | u8 op | u32 klen | u32 vlen | key | value)."""
    with open(path, "rb") as fh:
        data = fh.read()
    ends, off = [], 0
    while off + 13 <= len(data):
        klen = int.from_bytes(data[off + 5:off + 9], "little")
        vlen = int.from_bytes(data[off + 9:off + 13], "little")
        off += 13 + klen + vlen
        ends.append(off)
    return ends


def _cut_last_rows(path: str, rows: int) -> None:
    """Take the last `rows` records off a native store's file: what a
    kill before their flush leaves."""
    ends = _record_ends(path)
    os.truncate(path, ends[-rows - 1] if rows < len(ends) else 0)


@pytest.mark.parametrize("lagging", [
    "domain_log", "audit_log", "domain_state", "seq_no_db", "ts_store"])
def test_one_validators_stores_are_reconciled_whichever_ran_ahead(
        tmp_path, lagging):
    """The set of stores is not atomic. Whichever of them missed the last
    batch's flush, the start ends with all of them at one batch: the last
    one every ledger holds in full."""
    pool = _pool(tmp_path)
    _order(pool, "rc-1", 1)
    _order(pool, "rc-2", 2)
    victim = "Delta"
    before = pool.nodes[victim].c.db
    sizes = (before.get_ledger(DOMAIN_LEDGER_ID).size,
             before.get_ledger(AUDIT_LEDGER_ID).size)
    state_root = before.get_state(DOMAIN_LEDGER_ID).committed_head_hash
    pool.crash_node(victim)
    _cut_last_rows(os.path.join(str(tmp_path), victim, lagging, "kv.kvn"), 1)

    node = pool.start_node(victim)
    db = node.c.db
    dom, aud = db.get_ledger(DOMAIN_LEDGER_ID), db.get_ledger(AUDIT_LEDGER_ID)
    rolled_back = lagging in ("domain_log", "audit_log")
    want = tuple(s - 1 for s in sizes) if rolled_back else sizes
    assert (dom.size, aud.size) == want, node.recovery
    assert last_whole_batch(db) == aud.size
    other = pool.nodes["Alpha"].c.db
    assert dom.root_hash == other.get_ledger(
        DOMAIN_LEDGER_ID).tree.merkle_tree_hash(0, dom.size)
    state = db.get_state(DOMAIN_LEDGER_ID)
    if rolled_back:
        assert state.committed_head_hash != state_root
        assert node.recovery["reconcile"]["state"] == {
            DOMAIN_LEDGER_ID: "moved"}
    else:
        assert state.committed_head_hash == state_root
    # the named stores hold every kept txn and none that was cut
    last = dom.get_by_seq_no(dom.size)
    seq_no_db = db.get_store("seq_no_db")
    assert seq_no_db.has_key(
        last["txn"]["metadata"]["payloadDigest"].encode())
    assert seq_no_db.size == dom.size - 1       # the genesis has no entry
    assert db.ts_store.get(
        DOMAIN_LEDGER_ID, last["txnMetadata"]["txnTime"]) \
        == state.committed_head_hash

    # and the validator rejoins: catches up what it cut, orders on
    pool.net.connect_all()
    node.rejoin_after_restart()
    pool.run(15.0)
    assert not node.rejoining
    assert len(_views(pool)) == 1
    _order(pool, "rc-3", 3)
    assert {v[0] for v in _views(pool)} == {sizes[0] + 1}
    assert len(_views(pool)) == 1


def test_state_that_lacks_the_batch_is_replayed_from_the_ledger(tmp_path):
    """The trie's node store lost the whole last batch (not only the
    committed-head row): the ledger's txns are replayed into it."""
    pool = _pool(tmp_path)
    _order(pool, "sr-1", 1)
    victim = "Delta"
    path = os.path.join(str(tmp_path), victim, "domain_state", "kv.kvn")
    rows_at_1 = len(_record_ends(path))
    _order(pool, "sr-2", 2)
    want = pool.nodes[victim].c.db.get_state(
        DOMAIN_LEDGER_ID).committed_head_hash
    pool.crash_node(victim)
    _cut_last_rows(path, len(_record_ends(path)) - rows_at_1)

    node = pool.start_node(victim)
    assert node.c.db.get_state(DOMAIN_LEDGER_ID).committed_head_hash == want
    assert node.recovery["reconcile"]["state"] == {
        DOMAIN_LEDGER_ID: {"replayed_txns": 1}}


# --- the pool -----------------------------------------------------------------


def test_validators_at_different_batches_converge_and_serve_proofs(tmp_path):
    """Two validators hold batch 3 (so its writes may be acknowledged),
    two stopped at batch 2. After the restart all four hold batch 3, and
    the two that caught it up serve proof-bearing reads at its root with
    NO batch ordered since: the multi-signature came from a peer's BLS
    store (MULTI_SIG) and was verified."""
    pool = _pool_at_two_batches(tmp_path, behind=["Gamma", "Delta"])
    _restart_all(pool)
    assert {v[0] for v in _views(pool)} == {4}
    assert len(_views(pool)) == 1
    for name in pool.names:
        node = pool.nodes[name]
        assert not node.rejoining
        assert node.master_replica.last_ordered_3pc[1] == 3
        root = node.c.db.get_state(DOMAIN_LEDGER_ID).committed_head_hash
        anchor = node.read_plane.anchor_for(DOMAIN_LEDGER_ID)
        assert anchor is not None and anchor.state_root_hex == root.hex(), \
            name
        read = Request("reader", 900, {"type": GET_NYM,
                                       "dest": _user("dr-3").identifier})
        result = node.read_plane.answer(read)
        assert result.get("state_proof") or result.get("proof"), name
    assert pool.nodes["Gamma"].recovery["rejoined"]["txns_caught_up"][
        DOMAIN_LEDGER_ID] == 1
    _order(pool, "dr-4", 4)
    assert {v[0] for v in _views(pool)} == {5}
    assert len(_views(pool)) == 1


def test_a_tail_only_one_validator_holds_is_cut(tmp_path):
    """One validator flushed batch 3, the other three stopped at batch 2:
    f+1 REPLYs for it cannot exist, no quorum can be brought to it, and
    the pool orders another batch 3. The validator cuts it (n-f others
    hold less), the pool orders on, all four agree."""
    pool = _pool_at_two_batches(tmp_path, behind=["Beta", "Gamma", "Delta"])
    _restart_all(pool)
    alpha = pool.nodes["Alpha"]
    assert ("unbacked_tail_rolled_back", (2, {DOMAIN_LEDGER_ID: 1})) \
        in list(alpha.spylog)
    assert {v[0] for v in _views(pool)} == {3}
    assert len(_views(pool)) == 1
    assert alpha.master_replica.last_ordered_3pc[1] == 2
    _order(pool, "dr-other-3", 5)
    assert {v[0] for v in _views(pool)} == {4}
    assert len(_views(pool)) == 1
    # the write that was cut is not half there: it can be sent again
    _order(pool, "dr-3", 3)
    assert {v[0] for v in _views(pool)} == {5}
    assert len(_views(pool)) == 1


def test_a_tail_is_kept_while_a_validator_is_unaccounted_for(tmp_path):
    """Alpha alone holds batch 3 and Delta is down: Delta may hold it too,
    and then a client holds f+1 REPLYs. Alpha keeps its ledger and waits."""
    pool = _pool_at_two_batches(tmp_path, behind=["Beta", "Gamma", "Delta"])
    for name in ("Alpha", "Beta", "Gamma"):
        pool.start_node(name)
    pool.net.connect_all()
    for node in pool.nodes.values():
        node.rejoin_after_restart()
    pool.run(20.0)
    alpha = pool.nodes["Alpha"]
    assert alpha.c.db.get_ledger(DOMAIN_LEDGER_ID).size == 4
    assert alpha.rejoining and alpha.leecher.is_running
    assert not any(e[0] == "unbacked_tail_rolled_back"
                   for e in alpha.spylog)
    # Delta comes back holding less: now n-f others lack the tail
    pool.start_node("Delta")
    pool.net.connect_all()
    pool.nodes["Delta"].rejoin_after_restart()
    pool.run(40.0)
    assert {v[0] for v in _views(pool)} == {3}
    assert len(_views(pool)) == 1
    assert not any(node.rejoining for node in pool.nodes.values())


def test_storage_counters_and_flush_time_on_durable_stores_only(tmp_path):
    pool = _pool(tmp_path)
    _order(pool, "io-1", 1)
    node = pool.nodes["Alpha"]
    io = node.validator_info()["storage"]
    assert io["rows"] > 10 and io["bytes"] > io["rows"] * 13
    assert io["flushes"] >= 10 and io["gets"] > 0
    flush = node.metrics.accumulators["storage.flush_time"]
    assert flush.count >= 1 and 0 < flush.total <= io["flush_s"]
    durable = [e[3] for e in node.tracer.ring if e[1] == "durable"]
    assert durable and durable[-1]["rows"] > 0 and durable[-1]["bytes"] > 0

    memory = Pool()
    _order(memory, "io-2", 1)
    info = memory.nodes["Alpha"].validator_info()
    assert info["storage"] is None and info["recovery"] is None
    assert "storage.flush_time" not in memory.nodes[
        "Alpha"].metrics.accumulators


def test_pool_killed_at_the_second_matching_reply_restores_the_multi_sig(
        tmp_path):
    """The BLS check of a batch runs beside the loop (PR 48) and its
    multi-signature is stored where the check lands: inside the batch's
    group commit at the latest, so before any REPLY. All four killed the
    instant a client holds two matching REPLYs for the last write, no
    cycle run to its end: every validator that answered serves proofs at
    that batch's root from its own BLS store, before anything is ordered
    or caught up again."""
    from plenum_tpu.common.node_messages import Reply
    pool = _pool(tmp_path)
    _order(pool, "kill-1", 1)
    last = signed_nym(pool.trustee, _user("kill-2"), 2)
    pool.submit(last)

    def answered():             # one REPLY a write a node
        return [n for n in pool.names if len(pool.replies(n, Reply)) == 2]
    for _ in range(200):
        for node in list(pool.nodes.values()):
            node.prod()
            if len(answered()) >= 2:
                break
        if len(answered()) >= 2:
            break
        pool.timer.advance(0.1)
    acked = answered()
    assert len(acked) >= 2
    roots = {n: pool.nodes[n].c.db.get_state(
        DOMAIN_LEDGER_ID).committed_head_hash for n in acked}
    assert len(set(roots.values())) == 1
    _crash_all(pool)
    for name in pool.names:
        pool.start_node(name)           # no prod, no rejoin: what is on disk
    for name in acked:
        node = pool.nodes[name]
        assert node.c.db.get_state(
            DOMAIN_LEDGER_ID).committed_head_hash == roots[name]
        ms = node.c.bls_store.get(roots[name].hex())
        assert ms is not None and len(ms.participants) >= 3, name
        anchor = node.read_plane.anchor_for(DOMAIN_LEDGER_ID)
        assert anchor is not None \
            and anchor.state_root_hex == roots[name].hex(), name
