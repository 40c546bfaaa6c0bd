"""A view change with a backlog: the master primary stopped while a few
hundred finalised requests wait and uncommitted batches are in flight.

Sim network, mock timer, file stores (the native engine). After the view
change every request is ordered exactly once, the three survivors' domain
roots equal the plain reference's (`benchmarks/reference.py`) over the
ledger's transactions, the stopped node restarted from its directory
converges, and `validator_info()["view_change"]` holds the episode's
counts. The scenario `tcp_failover.primary_kill` runs over sockets, held
here at tier 1."""
from __future__ import annotations

import os
import sys

import pytest

from plenum_tpu.common.node_messages import (Commit, DOMAIN_LEDGER_ID,
                                              Ordered)
from plenum_tpu.config import Config
from plenum_tpu.crypto.ed25519 import Ed25519Signer
from plenum_tpu.network.sim_network import Discard

from test_pool import Pool, signed_nym

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import reference  # noqa: E402

REQUESTS = 320
BATCH = 20
DEPTH = 3           # uncommitted batches the primary keeps in flight


def _user(i: int) -> Ed25519Signer:
    return Ed25519Signer(seed=(b"failover-%d" % i).ljust(32, b"\0"))


def _domain(node):
    return node.c.db.get_ledger(DOMAIN_LEDGER_ID)


def _txns(node) -> list:
    ledger = _domain(node)
    return [ledger.get_by_seq_no(i) for i in range(1, ledger.size + 1)]


@pytest.fixture(scope="module")
def episode(tmp_path_factory):
    """The scenario, run once: -> (pool, victim, survivors, what was in
    flight at the stop)."""
    pool = Pool(config=Config(
        Max3PCBatchWait=0.05, Max3PCBatchSize=BATCH,
        Max3PCBatchesInFlight=DEPTH, kv_backend="native",
        PRIMARY_DISCONNECT_TIMEOUT=1.5, ORDERING_PROGRESS_TIMEOUT=300.0,
        STATE_FRESHNESS_UPDATE_INTERVAL=300.0),
        data_dir=str(tmp_path_factory.mktemp("failover")))
    victim = pool.nodes["Alpha"].master_replica.data.primary_name
    assert victim == "Alpha"
    survivors = [n for n in pool.names if n != victim]

    # some committed history first
    for i in range(20):
        pool.submit(signed_nym(pool.trustee, _user(i), i + 1))
    pool.run(4.0)
    assert {_domain(n).size for n in pool.nodes.values()} == {21}

    # COMMITs stop arriving: batches are PRE-PREPAREd and prepared on every
    # node and committed on none, while requests keep being finalised
    hold = pool.net.add_rule(Discard(),
                             lambda msg, frm, dst: isinstance(msg, Commit))
    for i in range(20, REQUESTS):
        pool.submit(signed_nym(pool.trustee, _user(i), i + 1))
    pool.run(1.0)
    in_flight = {n: len(pool.nodes[n].master_replica.ordering
                        ._applied_unordered) for n in pool.names}
    waiting = {n: sum(len(q) for q in pool.nodes[n].master_replica.ordering
                      .request_queues.values()) for n in pool.names}
    assert {_domain(n).size for n in pool.nodes.values()} == {21}

    # the primary stops (no clean shutdown: its files are as last flushed)
    pool.crash_node(victim)
    pool.net.remove_rule(hold)
    pool.run(12.0)
    return pool, victim, survivors, {"in_flight": in_flight,
                                     "waiting": waiting}


def test_uncommitted_batches_and_a_backlog_were_in_flight(episode):
    pool, victim, survivors, at_stop = episode
    assert at_stop["waiting"][victim] >= 200
    assert all(at_stop["in_flight"][n] == DEPTH for n in pool.names), at_stop
    # the primary's queue holds what it has not batched yet; the others'
    # hold every finalised request that is not ordered
    assert at_stop["waiting"][victim] == REQUESTS - 20 - DEPTH * BATCH
    assert all(at_stop["waiting"][n] == REQUESTS - 20 for n in survivors)


def test_every_request_is_ordered_exactly_once_in_the_new_view(episode):
    pool, victim, survivors, _ = episode
    for n in survivors:
        node = pool.nodes[n]
        assert node.master_replica.view_no == 1
        assert _domain(node).size == 1 + REQUESTS, n
        keys = [(t["txn"]["metadata"]["from"], t["txn"]["metadata"]["reqId"])
                for t in _txns(node)[1:]]
        assert sorted(keys) == [(pool.trustee.identifier, i + 1)
                                for i in range(REQUESTS)]
    # a REPLY for every request from every survivor, once
    for n in survivors:
        replied = [r.result["txn"]["metadata"]["reqId"]
                   for r in pool.replies(n)]
        assert sorted(replied) == list(range(1, REQUESTS + 1)), n


def test_survivors_roots_equal_the_plain_references(episode):
    pool, victim, survivors, _ = episode
    txns = _txns(pool.nodes[survivors[0]])
    ref_root, _state = reference.replay(txns)
    for n in survivors:
        node = pool.nodes[n]
        assert _domain(node).root_hash == ref_root, n
    assert len({pool.nodes[n].c.db.get_state(DOMAIN_LEDGER_ID)
                .committed_head_hash for n in survivors}) == 1


def test_validator_info_holds_the_episode(episode):
    pool, victim, survivors, at_stop = episode
    for n in survivors:
        info = pool.nodes[n].validator_info()
        vc = info["view_change"]
        assert vc["view_no"] == info["view_no"] == 1
        assert (vc["started"], vc["completed"]) == (1, 1)
        assert vc["in_progress"] is False
        last = vc["last"]
        assert last["view_no"] == 1
        # every survivor has every phase, the node whose own vote
        # completed the f+1 InstanceChange quorum too
        assert set(last["phases_s"]) == {
            "detect_to_vote", "vote_to_start", "start_to_new_view",
            "new_view_to_order"}, (n, last)
        assert 1.5 <= last["phases_s"]["detect_to_vote"] <= 1.8
        assert last["duration_s"] == pytest.approx(
            sum(last["phases_s"].values()))
        episode_seen = vc["ordering"]
        assert episode_seen["view_no"] == 1
        assert episode_seen["reverted_batches"] == at_stop["in_flight"][n]
        # the 20 early requests were 20 batches or fewer, none of them
        # past a checkpoint: they and the DEPTH prepared ones ride over
        assert DEPTH < episode_seen["reordered_batches"] <= 20 + DEPTH
        # prepared batches ride into the new view as they were; what the
        # new view's first fresh PRE-PREPARE found waiting is the backlog
        assert at_stop["waiting"][victim] \
            <= episode_seen["waiting_at_first_cut"] <= REQUESTS - 20
    # the events are the ones MetricsName already had, emitted once
    node = pool.nodes[survivors[0]]
    phases = [p for e, p in node.spylog if e == "vc_stall_phases"]
    assert len(phases) == 1


def test_no_view_change_nothing_recorded():
    pool = Pool()
    for i in range(3):
        pool.submit(signed_nym(pool.trustee, _user(1000 + i), i + 1))
    pool.run(4.0)
    for node in pool.nodes.values():
        vc = node.validator_info()["view_change"]
        assert vc == {"started": 0, "completed": 0, "view_no": 0,
                      "in_progress": False, "last": None, "ordering": None,
                      "waiting_on": None}
        assert node.master_replica.ordering._first_cut_due is False


def test_the_stopped_primary_restarted_from_its_directory_converges(episode):
    pool, victim, survivors, _ = episode
    node = pool.start_node(victim)
    pool.net.connect_all()
    assert _domain(node).size == 21         # what it had committed
    node.start_catchup()
    pool.run(10.0)
    want = pool.nodes[survivors[0]]
    assert _domain(node).size == 1 + REQUESTS
    assert _domain(node).root_hash == _domain(want).root_hash
    assert node.c.db.get_state(DOMAIN_LEDGER_ID).committed_head_hash == \
        want.c.db.get_state(DOMAIN_LEDGER_ID).committed_head_hash
    assert node.master_replica.view_no == 1
    # and the four order new writes together
    pool.submit(signed_nym(pool.trustee, _user(REQUESTS), REQUESTS + 1))
    pool.run(5.0)
    assert {_domain(n).size for n in pool.nodes.values()} == {2 + REQUESTS}
    assert len({_domain(n).root_hash for n in pool.nodes.values()}) == 1


def test_the_work_of_a_view_change_is_on_the_host_plane_of_a_traced_node(
        monkeypatch):
    """`vc.revert_batches`, `vc.build_new_view`, `vc.check_new_view` and
    `vc.first_cut` go through node.py's `_phase` helper (a
    jax.profiler.TraceAnnotation each), between them on every survivor
    and only during a view change."""
    from plenum_tpu.node import node as node_mod
    seen: list = []
    real = node_mod._phase

    def spy(name, run):
        seen.append(name)
        return real(name, run)
    monkeypatch.setattr(node_mod, "_phase", spy)
    pool = Pool(config=Config(Max3PCBatchWait=0.05,
                              PRIMARY_DISCONNECT_TIMEOUT=1.5,
                              ORDERING_PROGRESS_TIMEOUT=300.0,
                              STATE_FRESHNESS_UPDATE_INTERVAL=300.0))
    pool.submit(signed_nym(pool.trustee, _user(2000), 1))
    pool.run(3.0)
    assert not [s for s in seen if s.startswith("vc.")]
    pool.crash_node("Alpha")
    pool.submit(signed_nym(pool.trustee, _user(2001), 2),
                to=["Beta", "Gamma", "Delta"])
    pool.run(8.0)
    spans = [s for s in seen if s.startswith("vc.")]
    assert spans.count("vc.build_new_view") == 1        # the new primary
    assert spans.count("vc.check_new_view") == 2        # the other two
    assert spans.count("vc.first_cut") == 1
    assert spans.count("vc.revert_batches") >= 3
    assert {_domain(pool.nodes[n]).size
            for n in ("Beta", "Gamma", "Delta")} == {3}


# --- a survivor that lags the others across a checkpoint boundary -----------


@pytest.mark.parametrize("laggard", ["Beta", "Delta"],
                         ids=["the_new_primary_lags", "a_backup_lags"])
@pytest.mark.parametrize("ordered_first", [8, 19])
def test_view_change_completes_when_a_survivor_lags_across_a_checkpoint(
        laggard, ordered_first):
    """Two survivors have ordered past a checkpoint and stabilized it (they
    hold it and nothing older); the third's COMMITs were lost and it
    stopped short of the boundary (it holds the one before). A view change
    runs on exactly these three votes, so no checkpoint is held by n-f of
    them: the selection takes f+1 holders (one of them honest), as
    upstream's calc_checkpoint does. Asking for n-f wedged the view change
    for good ("no checkpoint over ..."), which is what
    `tcp_failover.primary_kill` first read on the chip. The laggard then
    fetches what lies below the new view's checkpoint, and the three order
    new writes in view 1."""
    pool = Pool(tracing=False, config=Config(
        Max3PCBatchWait=0.05, Max3PCBatchSize=1, CHK_FREQ=10, LOG_SIZE=30,
        PRIMARY_DISCONNECT_TIMEOUT=1.5, ORDERING_PROGRESS_TIMEOUT=300.0,
        STATE_FRESHNESS_UPDATE_INTERVAL=300.0))
    survivors = ["Beta", "Gamma", "Delta"]
    rid = 0
    for _ in range(ordered_first):
        rid += 1
        pool.submit(signed_nym(pool.trustee, _user(3000 + rid), rid))
        pool.run(0.5)
    pool.run(2.0)
    lost = pool.net.add_rule(
        Discard(), lambda m, frm, dst: isinstance(m, Commit)
        and dst == laggard)
    for _ in range(5):
        rid += 1
        pool.submit(signed_nym(pool.trustee, _user(3000 + rid), rid))
    pool.run(3.0)
    at = {n: pool.nodes[n].master_replica.last_ordered_3pc[1]
          for n in survivors}
    ahead = [n for n in survivors if n != laggard]
    assert at[laggard] == ordered_first
    assert all(at[n] == ordered_first + 5 for n in ahead)
    boundary = (ordered_first // 10 + 1) * 10
    assert all(pool.nodes[n].master_replica.data.stable_checkpoint
               == boundary for n in ahead)
    assert pool.nodes[laggard].master_replica.data.stable_checkpoint \
        == boundary - 10

    pool.crash_node("Alpha")
    pool.net.remove_rule(lost)
    pool.run(12.0)      # the view change ~1.6 s; the laggard's catchup ~6 s
    for n in survivors:
        node = pool.nodes[n]
        vc = node.validator_info()["view_change"]
        assert (vc["view_no"], vc["started"], vc["completed"],
                vc["in_progress"], vc["waiting_on"]) == (1, 1, 1, False,
                                                         None), (n, vc)
        assert _domain(node).size == 1 + ordered_first + 5, n
    assert ("catchup_started", None) in list(pool.nodes[laggard].spylog)
    assert not any(e[0] == "catchup_started" for n in ahead
                   for e in pool.nodes[n].spylog)

    for _ in range(3):
        rid += 1
        pool.submit(signed_nym(pool.trustee, _user(3000 + rid), rid),
                    to=survivors)
    pool.run(6.0)
    assert {pool.nodes[n].master_replica.last_ordered_3pc
            for n in survivors} == {(1, ordered_first + 8)}
    assert {_domain(pool.nodes[n]).size for n in survivors} \
        == {1 + ordered_first + 8}
    assert len({_domain(pool.nodes[n]).root_hash for n in survivors}) == 1


def test_view_change_in_progress_says_what_it_waits_on():
    """VALIDATOR_INFO `view_change.waiting_on` while a view change cannot
    finish: two survivors of four cannot gather n-f votes."""
    pool = Pool(tracing=False, config=Config(
        Max3PCBatchWait=0.05, PRIMARY_DISCONNECT_TIMEOUT=1.5,
        ORDERING_PROGRESS_TIMEOUT=300.0,
        STATE_FRESHNESS_UPDATE_INTERVAL=300.0))
    pool.crash_node("Alpha")
    pool.crash_node("Delta")
    pool.run(5.0)
    for n in ("Beta", "Gamma"):
        vc = pool.nodes[n].validator_info()["view_change"]
        assert vc["in_progress"] and vc["started"] == 1 \
            and vc["completed"] == 0
        assert vc["waiting_on"] == {
            "view_no": 1, "primary": "Beta",
            "votes_from": ["Beta", "Gamma"],
            "citable": [n], "new_view_held": False,
            "new_view_pending_on_votes": False, "no_selection": None,
            "escalations": 0}


def test_every_node_names_the_same_checkpoint():
    """A checkpoint is compared whole across a view change's voters: its
    range is its own CHK_FREQ batches and its digest the boundary batch's
    own audit root, on every node, whatever had stabilized there or had
    been applied speculatively when the node ordered that batch (COMMITs
    reach Delta 0.4 s late here, so it orders batch 10 with batches 11 and
    12 applied, and cuts checkpoint 10 before checkpoint 5 is stable)."""
    from plenum_tpu.network.sim_network import Deliver
    pool = Pool(tracing=False, config=Config(
        Max3PCBatchWait=0.05, Max3PCBatchSize=1, CHK_FREQ=5, LOG_SIZE=15))
    pool.net.add_rule(Deliver(0.4, 0.4), lambda m, frm, dst:
                      isinstance(m, Commit) and dst == "Delta")
    roots = {}
    for node in pool.nodes.values():
        node.master_replica.internal_bus.subscribe(
            Ordered, lambda m, n=node.name: roots.setdefault(
                (n, m.pp_seq_no), m.audit_txn_root) if m.inst_id == 0
            else None)
    for rid in range(1, 13):
        pool.submit(signed_nym(pool.trustee, _user(5000 + rid), rid))
    pool.run(10.0)
    held = {n: [(c.view_no, c.seq_no_start, c.seq_no_end, c.digest)
                for c in pool.nodes[n].master_replica.data.checkpoints]
            for n in pool.names}
    assert len({tuple(v) for v in held.values()}) == 1, held
    assert held["Delta"] == [(0, 6, 10, roots[("Alpha", 10)])]
    assert roots[("Delta", 10)] == roots[("Alpha", 10)] != ""
    assert {pool.nodes[n].master_replica.data.stable_checkpoint
            for n in pool.names} == {10}
