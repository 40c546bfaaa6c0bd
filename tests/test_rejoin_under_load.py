"""A validator restarted while the pool keeps writing.

Sim network, mock timer, file stores (the native engine), open-loop writes
that never pause: a non-primary validator is stopped without a shutdown,
stays down for the seconds a process needs to come back, is started again
from its directory and rejoins UNDER LOAD. It catches up, orders by its own
COMMIT quorum again, signs later multi-signatures, and nobody changes the
view; every request is ordered exactly once and the roots equal the plain
reference's (`benchmarks/reference.py`). The scenario
`tcp_rejoin.backup_restart` runs over sockets, held here at tier 1, with one
test for each repair that scenario forced (docs/rejoin.md)."""
from __future__ import annotations

import os
import sys

import pytest

from plenum_tpu.common.node_messages import (AUDIT_LEDGER_ID, Commit,
                                              DOMAIN_LEDGER_ID,
                                              InstanceChange, PrePrepare)
from plenum_tpu.common.stashing import StashReason
from plenum_tpu.config import Config
from plenum_tpu.crypto.ed25519 import Ed25519Signer
from plenum_tpu.execution import txn as txn_lib
from plenum_tpu.execution.handlers import audit as audit_lib
from plenum_tpu.network.sim_network import Discard

from test_pool import Pool, signed_nym

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import reference  # noqa: E402

VICTIM = "Delta"            # primary of no instance in view 0
PER_STEP = 4                # writes every 0.1 s of mock time: 40/s
BATCH = 20


def _user(i: int) -> Ed25519Signer:
    return Ed25519Signer(seed=(b"rejoin-%d" % i).ljust(32, b"\0"))


def _ledger(node, lid=DOMAIN_LEDGER_ID):
    return node.c.db.get_ledger(lid)


def _txns(node) -> list:
    ledger = _ledger(node)
    return [ledger.get_by_seq_no(i) for i in range(1, ledger.size + 1)]


class Load:
    """Open-loop writes on mock time: PER_STEP requests to every live node
    each 0.1 s, whatever has been answered."""

    def __init__(self, tmp, **config):
        self.pool = Pool(config=Config(**dict(
            dict(Max3PCBatchWait=0.05, Max3PCBatchSize=BATCH,
                 kv_backend="native"), **config)),
            data_dir=str(tmp), tracing=False)
        self.sent = 0

    def steps(self, n: int, until=None) -> int:
        for i in range(n):
            if until is not None and until():
                return i
            for _ in range(PER_STEP):
                self.sent += 1
                self.pool.submit(signed_nym(self.pool.trustee,
                                            _user(self.sent), self.sent),
                                 to=list(self.pool.nodes))
            self.pool.run(0.1)
        return n

    def restart(self, name: str = VICTIM):
        node = self.pool.start_node(name)
        self.pool.net.connect_all()
        node.rejoin_after_restart()
        return node


@pytest.fixture(scope="module")
def episode(tmp_path_factory):
    """The scenario, run once -> (load, the restarted node, what was seen
    on the way)."""
    load = Load(tmp_path_factory.mktemp("rejoin"))
    pool = load.pool
    assert VICTIM not in pool.nodes["Alpha"].master_replica.data.primaries
    seen = {"instance_changes": []}
    pool.net.add_rule(Discard(), lambda m, frm, dst: bool(
        isinstance(m, InstanceChange)
        and seen["instance_changes"].append((frm, dst))))
    load.steps(20)
    seen["at_stop"] = _ledger(pool.nodes[VICTIM]).size
    pool.crash_node(VICTIM)
    load.steps(30)                  # the seconds a process needs to boot
    seen["pool_at_restart"] = _ledger(pool.nodes["Alpha"]).size
    node = load.restart()
    assert _ledger(node).size <= seen["at_stop"]
    seen["steps_to_first_order"] = load.steps(
        100, until=lambda: "first_3pc_order" in node.rejoin["phases_s"])
    seen["pool_at_first_order"] = _ledger(pool.nodes["Alpha"]).size
    seen["ordered_at_first_order"] = node.master_replica.last_ordered_3pc
    load.steps(20)                  # it orders with the others, under load
    seen["pool_at_stop_of_load"] = _ledger(pool.nodes["Alpha"]).size
    pool.run(5.0)
    return load, node, seen


def test_it_rejoins_while_the_writes_go_on(episode):
    load, node, seen = episode
    # the load never paused: the pool grew through the outage, the
    # catch-up and the first batches the node ordered itself
    assert seen["at_stop"] < seen["pool_at_restart"] \
        < seen["pool_at_first_order"] < seen["pool_at_stop_of_load"]
    assert seen["steps_to_first_order"] < 100
    rejoin = node.validator_info()["rejoin"]
    assert rejoin["phases_s"]["first_3pc_order"] < 10.0
    # by 3PC, not by catch-up: batches it executed through `Ordered`
    executed = [d for e, d in node.spylog if e == "executed"]
    assert executed and executed[0][1] == rejoin["rounds"][-1][
        "target_3pc"][1] + 1
    assert len(executed) >= 10
    # every round of the rejoin ended before the first of them
    events = [e for e, _ in node.spylog]
    assert "catchup_started" not in events[events.index("executed"):]


def test_no_view_change_and_no_suspicion_anywhere(episode):
    load, node, _ = episode
    for n in load.pool.nodes.values():
        vc = n.validator_info()["view_change"]
        assert (vc["started"], vc["completed"], vc["view_no"]) == (0, 0, 0)
        assert not [d for e, d in n.spylog if e == "suspicion"], n.name
    # the node that came back cast no vote against a master it has no
    # throughput history for, and nobody voted against it
    assert episode[2]["instance_changes"] == []


def test_every_request_is_ordered_exactly_once_and_the_roots_are_the_references(
        episode):
    load, node, _ = episode
    pool = load.pool
    txns = _txns(pool.nodes["Alpha"])
    keys = [(t["txn"]["metadata"]["from"], t["txn"]["metadata"]["reqId"])
            for t in txns[1:]]
    assert sorted(keys) == [(pool.trustee.identifier, i + 1)
                            for i in range(load.sent)]
    ref_root, _state = reference.replay(txns)
    for n in pool.nodes.values():
        assert _ledger(n).size == 1 + load.sent, n.name
        assert _ledger(n).root_hash == ref_root, n.name
    for lid in (DOMAIN_LEDGER_ID, AUDIT_LEDGER_ID):
        assert len({_ledger(n, lid).root_hash
                    for n in pool.nodes.values()}) == 1
    assert len({n.c.db.get_state(DOMAIN_LEDGER_ID).committed_head_hash
                for n in pool.nodes.values()}) == 1
    # what its disk held at the stop is a prefix of what all four hold
    assert _txns(node)[:episode[2]["at_stop"]] \
        == txns[:episode[2]["at_stop"]]


def test_its_commits_are_in_later_multi_signatures(episode):
    load, node, _ = episode
    alpha = load.pool.nodes["Alpha"]
    root = alpha.c.db.get_state(DOMAIN_LEDGER_ID).committed_head_hash.hex()
    multi_sig = alpha.c.db.bls_store.get(root)
    assert multi_sig is not None
    assert VICTIM in multi_sig.participants
    # and the victim holds a multi-signature for the same root itself
    assert node.c.db.bls_store.get(root) is not None


def test_the_phases_and_the_counters_are_present_and_add_up(episode):
    load, node, seen = episode
    info = node.validator_info()
    rejoin = info["rejoin"]
    phases = rejoin["phases_s"]
    order = ["process_start", "stores_replayed", "peers_reachable",
             "catchup_started", "catchup_complete", "first_3pc_order"]
    assert list(phases) == order
    assert [phases[k] for k in order] == sorted(phases[k] for k in order)
    assert phases["catchup_complete"] > phases["catchup_started"]
    # the start line's `recovery` carries the same clock
    assert info["recovery"]["seconds"]["phases"] == phases
    rejoined = info["recovery"]["rejoined"]
    assert rejoined["rounds"] == len(rejoin["rounds"]) >= 1
    assert rejoined["seconds"] == pytest.approx(
        phases["catchup_complete"] - phases["catchup_started"])
    caught_up = sum(rejoined["txns_caught_up"].values())
    assert caught_up == sum(r["txns"] for r in rejoin["rounds"])
    assert rejoined["txns_caught_up"][DOMAIN_LEDGER_ID] \
        >= seen["pool_at_restart"] - seen["at_stop"]
    for r in rejoin["rounds"]:
        # how far the target moved while the round ran
        assert r["pool_seen_at"] >= r["target_3pc"][1]
        assert r["target_sizes"][AUDIT_LEDGER_ID] >= r["target_3pc"][1]
    stash = rejoin["stash"]
    assert stash["held"] == stash["replayed"] > 0
    assert 0 <= stash["below_last_ordered"] <= stash["held"]
    assert stash["restashed_missing_requests"] >= 0

    # the seeders served at least what the leecher took, and say so in
    # VALIDATOR_INFO and on their metrics
    seeders = {n: load.pool.nodes[n].validator_info()["catchup"]["seeder"]
               for n in load.pool.nodes}
    assert seeders[VICTIM]["txns_served"] == 0
    served = sum(s["txns_served"] for s in seeders.values())
    assert served >= caught_up > 0
    for name, s in seeders.items():
        if name == VICTIM:
            continue
        assert s["reqs"] > 0 and s["bytes_served"] > 100 * s["txns_served"]
        assert s["serve"]["count"] >= s["reqs"] - s["declined"]
        assert s["serve"]["sum_s"] > 0.0
        folded = load.pool.nodes[name].metrics.summary()
        assert folded["seeder.txns_served"]["sum"] == s["txns_served"]
        assert folded["seeder.bytes_served"]["sum"] == s["bytes_served"]
        assert folded["seeder.reqs"]["count"] == s["reqs"]
        assert folded["seeder.serve_time"]["count"] == s["serve"]["count"]
    # a node that never restarted has no rejoin to account for
    assert load.pool.nodes["Alpha"].validator_info()["rejoin"] is None


# --- one test a repair --------------------------------------------------------


def _restarted_under_load(tmp_path):
    load = Load(tmp_path)
    load.steps(20)
    load.pool.crash_node(VICTIM)
    load.steps(30)
    return load, load.restart()


def test_the_other_ledgers_stop_where_the_audit_ledger_does(tmp_path):
    """Repair 1 (catchup/leecher.py `_audit_cut`): the pool orders on while
    a node catches up, so a target agreed for each ledger in a round of
    its own leaves the domain ledger past the audit ledger's last batch.
    At the end of every round each ledger stands at the size and root the
    audit ledger's last transaction names."""
    load, node = _restarted_under_load(tmp_path)
    checked = []

    def check(_last_3pc, kept=node.leecher._on_catchup_complete):
        audit = _ledger(node, AUDIT_LEDGER_ID)
        last = audit_lib.last_audit_txn(audit)
        for lid, ledger in node.c.db.ledgers():
            if lid == AUDIT_LEDGER_ID:
                continue
            assert ledger.size == txn_lib.txn_data(last)["ledgerSize"][
                str(lid)], lid
            assert ledger.root_hash.hex() == audit_lib.resolve_ledger_root(
                audit, last, lid), lid
        checked.append(audit.size)
        kept(_last_3pc)
    node.leecher._on_catchup_complete = check
    load.steps(100, until=lambda: "first_3pc_order"
               in node.rejoin["phases_s"])
    assert checked and checked == sorted(checked)
    assert not [d for e, d in node.spylog if e == "suspicion"]


def test_without_the_audit_cut_the_restarted_node_blames_the_primary(
        tmp_path, monkeypatch):
    """What repair 1 repaired, kept visible: with a round a ledger the
    domain ledger overshoots the audit ledger under load, the node derives
    other roots than the pool for the next PRE-PREPARE and suspects the
    primary."""
    from plenum_tpu.catchup.leecher import NodeLeecherService
    monkeypatch.setattr(NodeLeecherService, "_audit_cut",
                        lambda self, lid: None)
    load, node = _restarted_under_load(tmp_path)
    load.steps(60)
    audit = _ledger(node, AUDIT_LEDGER_ID)
    named = txn_lib.txn_data(audit_lib.last_audit_txn(audit))[
        "ledgerSize"][str(DOMAIN_LEDGER_ID)]
    assert _ledger(node).size > named
    assert [d for e, d in node.spylog if e == "suspicion"]
    assert "first_3pc_order" not in node.rejoin["phases_s"]


def test_the_batch_in_flight_at_the_target_is_fetched_by_a_round_at_once(
        tmp_path):
    """Repair 2 (node.py `_on_catchup_complete`, OrderingService.gap_behind):
    the batch in flight when the first target was agreed left its 3PC
    messages before the node listened. The node sees COMMITs from f+1 past
    a batch it holds no PRE-PREPARE for and starts the next round at the
    instant the first one ends, not at the stuck-behind check."""
    load, node = _restarted_under_load(tmp_path)
    times = {}        # from here on: the first round has started already

    class Stamped(type(node.spylog)):
        def append(self, item):
            times.setdefault(item[0], []).append(
                load.pool.timer.get_current_time())
            super().append(item)
    node.spylog = Stamped(node.spylog, maxlen=node.spylog.maxlen)
    load.steps(100, until=lambda: "first_3pc_order"
               in node.rejoin["phases_s"])
    events = [e for e, _ in node.spylog]
    assert "catchup_gap_behind" in events
    assert "stuck_behind_resync" not in events
    last, gap = next(d for e, d in node.spylog if e == "catchup_gap_behind")
    assert gap > last + 1
    assert times["catchup_started"] == [pytest.approx(
        times["catchup_complete"][0])]
    assert len(node.rejoin["rounds"]) == 2
    assert node.rejoining is False
    # while the gap round ran the node said it was still rejoining
    assert times["catchup_complete"][1] > times["catchup_complete"][0]


def test_a_round_that_moved_nothing_starts_no_round_of_its_own(tmp_path):
    """Repair 2's bound: only a round that moved the ledgers is followed
    by another at once; otherwise the periodic check decides, as before."""
    load = Load(tmp_path)
    load.steps(10)
    load.pool.run(3.0)
    node = load.pool.nodes[VICTIM]
    node.master_replica.ordering.gap_behind = lambda: 99
    node.start_catchup()
    load.pool.run(6.0)
    events = [e for e, _ in node.spylog]
    assert events.count("catchup_started") == 1
    assert "catchup_gap_behind" not in events


def test_a_pre_prepare_held_already_is_not_applied_again(tmp_path):
    """Repair 3 (OrderingService.process_preprepare): a restarted node asks
    for a PRE-PREPARE it sees PREPAREs for while the primary's own copy
    sits in its stash; both come out of the stash when the catch-up ends.
    The second copy stacked the batch on its own effects, the roots
    differed and the node suspected the primary."""
    load = Load(tmp_path, Max3PCBatchWait=10.0)
    pool = load.pool
    node = pool.nodes[VICTIM]
    ordering = node.master_replica.ordering
    seen = []
    kept = ordering.process_preprepare

    def spy(msg, sender):
        seen.append((msg, sender))
        return kept(msg, sender)
    ordering._stasher._handlers[PrePrepare] = spy
    for i in range(BATCH):
        pool.submit(signed_nym(pool.trustee, _user(9000 + i), i + 1))
    pool.run(2.0)
    assert len(seen) == 1 and _ledger(node).size == 1 + BATCH
    pp, sender = seen[0]
    before = (len(ordering._applied_unordered), _ledger(node).uncommitted_size)
    # held and ordered: a copy is dropped before it reaches the executor
    ordering._stasher.dispatch(pp, sender)
    # held and NOT ordered yet: the next batch, its COMMITs kept away
    pool.net.add_rule(Discard(), lambda m, frm, dst: isinstance(m, Commit)
                      and dst == VICTIM)
    for i in range(BATCH):
        pool.submit(signed_nym(pool.trustee, _user(9100 + i), BATCH + i + 1))
    pool.run(2.0)
    assert len(seen) == 3 and len(ordering._applied_unordered) == 1
    ordering._stasher.dispatch(*seen[2])
    assert len(ordering._applied_unordered) == 1
    assert _ledger(node).uncommitted_size == 1 + 2 * BATCH
    assert before == (0, 1 + BATCH)
    assert not [d for e, d in node.spylog if e == "suspicion"]
    assert ordering._stasher.stash_size(StashReason.MISSING_REQUESTS) == 0


def test_answers_that_name_no_common_target_are_asked_again_at_the_links_pace(
        tmp_path):
    """Repair 4 (ConsProofService._note_reply): a process just started has
    measured no round trip, so its first status leaves with the 5 s
    fallback armed. Under load the three answers can name three sizes (a
    pool that orders moves between two answers): no f+1 of them agree, and
    the node waited out the fallback although every peer had answered in
    a millisecond. Here two of the first three answers are lost, which
    leaves the same: one vote for every target named. The first answer
    now brings the retry forward to the link's pace."""
    from plenum_tpu.common.node_messages import (ConsistencyProof,
                                                 LedgerStatus)
    load = Load(tmp_path)
    load.steps(20)
    load.pool.crash_node(VICTIM)
    load.steps(30)
    lost = []

    def first_answer_of(peer):
        def select(m, frm, to):
            hit = isinstance(m, ConsistencyProof) and frm == peer \
                and to == VICTIM and m.ledger_id == AUDIT_LEDGER_ID \
                and peer not in lost
            if hit:
                lost.append(peer)
            return hit
        return select
    for peer in ("Beta", "Gamma"):
        load.pool.net.add_rule(Discard(), first_answer_of(peer))
    asked = []          # when each audit status reached Alpha
    load.pool.net.add_rule(Discard(), lambda m, frm, to: bool(
        isinstance(m, LedgerStatus) and not m.is_reply and frm == VICTIM
        and to == "Alpha" and m.ledger_id == AUDIT_LEDGER_ID
        and asked.append(load.pool.timer.get_current_time())))
    node = load.restart()
    audit = node.leecher.leechers[AUDIT_LEDGER_ID].cons_proof
    load.steps(100, until=lambda: "first_3pc_order"
               in node.rejoin["phases_s"])
    assert sorted(lost) == ["Beta", "Gamma"]
    assert audit._rtt.samples >= 1 and audit._rtt.srtt < 0.5
    # the second status left well before the fallback (3.5-5 s with its
    # jitter) would have fired
    assert len(asked) >= 2 and asked[1] - asked[0] < 1.5
    assert "first_3pc_order" in node.rejoin["phases_s"]
