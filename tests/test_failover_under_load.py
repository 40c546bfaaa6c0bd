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

from plenum_tpu.common.internal_messages import NewViewAccepted
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


@pytest.fixture(scope="module", autouse=True)
def highest_pre_prepared_is_what_the_log_holds():
    """Every scenario of this file, at every PRE-PREPARE that arrives: the
    sequence number OrderingService keeps is the one a scan of its log
    gives (the scan is what it used to do)."""
    from plenum_tpu.consensus.ordering_service import OrderingService
    kept = OrderingService._last_preprepared_seq

    def checked(self):
        seqs = [k[1] for k in self.prePrepares if k[0] == self._data.view_no]
        floor = max(self._data.low_watermark, self._data.last_ordered_3pc[1])
        got = kept(self)
        assert got == max(seqs + [floor]), (self._data.name, got, seqs, floor)
        return got
    OrderingService._last_preprepared_seq = checked
    yield
    OrderingService._last_preprepared_seq = kept


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


# --- what re-certifying a batch costs a node that had ordered it ------------


def _spy(obj, name: str, seen: list, key=lambda *a: a):
    """Record every call of obj.<name> (an instance attribute over the
    method) and pass it through."""
    real = getattr(obj, name)

    def through(*args):
        seen.append(key(*args))
        return real(*args)
    setattr(obj, name, through)


@pytest.fixture(scope="module")
def recertified(tmp_path_factory):
    """Six one-request batches in view 0, the last two ordered by three of
    the four (Delta prepared them and sent its COMMITs, and the COMMITs to
    it were lost); the primary stops; the view change re-certifies all
    six; then the new view runs past a checkpoint. -> what was seen."""
    from plenum_tpu.common.node_messages import PrePrepare
    pool = Pool(tracing=False, config=Config(
        Max3PCBatchWait=0.05, Max3PCBatchSize=1, CHK_FREQ=10, LOG_SIZE=30,
        kv_backend="native", PRIMARY_DISCONNECT_TIMEOUT=1.5,
        ORDERING_PROGRESS_TIMEOUT=300.0,
        STATE_FRESHNESS_UPDATE_INTERVAL=300.0),
        data_dir=str(tmp_path_factory.mktemp("recertified")))
    survivors = ["Beta", "Gamma", "Delta"]
    wire: list = []
    pool.net.add_rule(Discard(), lambda m, frm, dst: isinstance(
        m, (Commit, PrePrepare)) and wire.append((m, frm)) and False)

    def write(rid, to=None):
        pool.submit(signed_nym(pool.trustee, _user(7000 + rid), rid), to=to)
    for rid in range(1, 5):
        write(rid)
        pool.run(0.5)
    pool.run(2.0)
    lost = pool.net.add_rule(Discard(), lambda m, frm, dst:
                             isinstance(m, Commit) and dst == "Delta")
    for rid in (5, 6):
        write(rid)
        pool.run(0.5)
    pool.run(2.0)
    at_stop = {n: pool.nodes[n].master_replica.last_ordered_3pc
               for n in pool.names}
    view0_sigs = {(frm, m.pp_seq_no): m.bls_sig for m, frm in wire
                  if isinstance(m, Commit) and m.inst_id == 0}

    signed = {n: [] for n in survivors}
    stored = {n: [] for n in survivors}
    aggregated = {n: [] for n in survivors}
    for n in survivors:
        node = pool.nodes[n]
        _spy(node.c.bls_signer, "sign", signed[n])
        _spy(node.c.bls_store, "put", stored[n],
             key=lambda ms: ms.value.state_root_hash)
        _spy(node.master_replica.bls, "submit_order", aggregated[n],
             key=lambda key, pp: key)
    # the master's bus on each survivor: the NEW_VIEW accepted and every
    # batch ordered after it, each with the node timer's reading and the
    # BLS replica's counters as they were at that send
    bus_seen = {n: [] for n in survivors}
    for n in survivors:
        replica = pool.nodes[n].master_replica
        for kind in (NewViewAccepted, Ordered):
            replica.internal_bus.subscribe(
                kind, lambda m, n=n, replica=replica: bus_seen[n].append(
                    (m, pool.nodes[n].timer.get_current_time(),
                     replica.bls.tally())), first=True)
    roots = {pp.pp_seq_no: pp.state_root for pp in pool.nodes[
        "Beta"].master_replica.ordering.prePrepares.values()}

    pool.crash_node("Alpha")
    pool.net.remove_rule(lost)
    del wire[:]
    pool.run(8.0)
    seen = {
        "at_stop": at_stop, "view0_sigs": view0_sigs, "roots": roots,
        "view1_commits": [(m, frm) for m, frm in wire
                          if isinstance(m, Commit) and m.inst_id == 0
                          and m.view_no == 1],
        "signed": {n: list(v) for n, v in signed.items()},
        "stored": {n: list(v) for n, v in stored.items()},
        "aggregated": {n: list(v) for n, v in aggregated.items()},
        "episode": {n: dict(pool.nodes[n].validator_info()
                            ["view_change"]["ordering"]) for n in survivors},
        "sizes": {n: _domain(pool.nodes[n]).size for n in survivors}}

    # the new view's first fresh batch, then on past checkpoint 10
    del wire[:]
    write(7, to=survivors)
    pool.run(3.0)
    seen["fresh"] = [m for m, frm in wire if isinstance(m, PrePrepare)
                     and m.inst_id == 0 and m.view_no == 1
                     and m.pp_seq_no == 7]
    seen["bus"] = {n: list(v) for n, v in bus_seen.items()}
    seen["view_change_closed"] = {
        n: pool.nodes[n].validator_info()["view_change"] for n in survivors}
    seen["kept_before_gc"] = {
        n: len(pool.nodes[n].master_replica.bls._own_sigs)
        for n in survivors}
    # on past checkpoints 10, 20 and 30, two batches beyond each
    seen["kept_after_gc"] = {n: [] for n in survivors}
    for first, last in ((8, 12), (13, 22), (23, 32)):
        for rid in range(first, last + 1):
            write(rid, to=survivors)
            pool.run(0.5)
        pool.run(3.0)
        for n in survivors:
            bls = pool.nodes[n].master_replica.bls
            seen["kept_after_gc"][n].append((
                pool.nodes[n].master_replica.data.stable_checkpoint,
                sorted(seq for seq, _sig in bls._own_sigs.values()),
                sorted(bls._verified_ms_keys.values())))
    return pool, survivors, seen


def test_a_node_that_ordered_a_batch_re_certifies_it_without_bls_work(
        recertified):
    pool, survivors, seen = recertified
    assert seen["at_stop"] == {"Alpha": (0, 6), "Beta": (0, 6),
                               "Gamma": (0, 6), "Delta": (0, 4)}
    # all six ride into view 1, on every survivor
    assert {e["reordered_batches"] for e in seen["episode"].values()} == {6}
    # no signature was made: each COMMIT of view 1 carries the bytes its
    # sender's COMMIT carried in view 0 (Delta had sent its own for the
    # two batches it never got to order)
    assert seen["signed"] == {n: [] for n in survivors}
    commits = seen["view1_commits"]
    assert {(frm, m.pp_seq_no) for m, frm in commits} \
        == {(n, s) for n in survivors for s in range(1, 7)}
    for m, frm in commits:
        assert m.bls_sig == seen["view0_sigs"][(frm, m.pp_seq_no)] \
            is not None
    # and the two that had ordered all six put nothing into their BLS store
    assert seen["stored"]["Beta"] == seen["stored"]["Gamma"] == []


def test_a_node_that_had_not_ordered_a_cited_batch_takes_the_whole_path(
        recertified):
    """Delta applies batches 5 and 6, checks the COMMITs' signatures at the
    quorum and stores its aggregate over each (`process_order`)."""
    pool, survivors, seen = recertified
    assert seen["sizes"] == {n: 7 for n in survivors}
    assert seen["stored"]["Delta"] == [seen["roots"][5], seen["roots"][6]]
    store = pool.nodes["Delta"].c.bls_store
    for seq in (5, 6):
        ms = store.get(seen["roots"][seq])
        assert ms is not None and len(ms.participants) >= 3
        assert pool.nodes["Beta"].master_replica.bls.multi_sig_holds(ms)


def test_the_episode_counts_the_bls_work_of_the_re_certified_batches(
        recertified):
    pool, survivors, seen = recertified
    for n in survivors:
        e = seen["episode"][n]
        assert e["bls_sigs_reused"] + e["bls_sigs_fresh"] \
            == e["reordered_batches"] == 6, (n, e)
        assert (e["bls_sigs_reused"], e["bls_sigs_fresh"]) == (6, 0)


# --- the last phase, step by step and by BLS landing (PR 49) ----------------

def _steps_in_order(e: dict) -> None:
    from plenum_tpu.consensus.ordering_service import VC_STEPS
    assert all(e[k] is not None and e[k] >= 0 for k in VC_STEPS), e
    assert e["recertified_ms"] <= e["first_cut_ms"] \
        <= e["first_cut_apply_ms"] <= e["fresh_ordered_ms"], e
    assert e["first_prepared_ms"] <= e["first_ordered_ms"] \
        <= e["fresh_ordered_ms"], e
    assert e["cycles"] >= 1
    assert 0 < e["longest_cycle_ms"] <= e["fresh_ordered_ms"], e
    if e["first_ordered_kind"] == "fresh":
        assert e["fresh_ordered_ms"] == e["first_ordered_ms"]
        assert e["first_cut_apply_ms"] <= e["first_prepared_ms"], e


@pytest.mark.parametrize("name, kind, first_seq, cited", [
    # had ordered all six: nothing cited is ordered again, the first
    # `Ordered` of view 1 is the fresh batch
    ("Beta", "fresh", 7, {"cited_reapplied": 0, "cited_ordered": 0,
                          "cited_recertified_only": 6}),
    ("Gamma", "fresh", 7, {"cited_reapplied": 0, "cited_ordered": 0,
                           "cited_recertified_only": 6}),
    # had prepared batches 5 and 6 and not ordered them: they are
    # applied again and ordered first, the fresh batch after them
    ("Delta", "recertified", 5, {"cited_reapplied": 2, "cited_ordered": 2,
                                 "cited_recertified_only": 4})])
def test_the_episode_splits_new_view_to_first_order_by_step(
        recertified, name, kind, first_seq, cited):
    pool, survivors, seen = recertified
    vc = seen["view_change_closed"][name]
    e = vc["ordering"]
    _steps_in_order(e)
    assert (e["first_ordered_kind"], e["first_ordered_pp_seq_no"],
            e["first_ordered_requests"]) == (kind, first_seq, 1)
    assert {k: e[k] for k in cited} == cited
    assert e["cited_reapplied"] + e["cited_recertified_only"] \
        == e["reordered_batches"]
    # the one stamp pair of before is what it was: NEW_VIEW accepted ->
    # the first master `Ordered` of EITHER kind, on the node's timer
    accepted, *ordered = seen["bus"][name]
    assert isinstance(accepted[0], NewViewAccepted)
    assert [m.pp_seq_no for m, _t, _b in ordered][0] == first_seq
    assert vc["last"]["phases_s"]["new_view_to_order"] == pytest.approx(
        ordered[0][1] - accepted[1], abs=1e-9)
    # the BLS replica inside the phase: its own counters' growth from the
    # NEW_VIEW accepted to the first FRESH batch ordered
    fresh = next(b for m, _t, b in ordered if m.original_view_no is None)
    bls = e["bls"]
    assert {k: bls[k] for k in fresh} == {
        k: pytest.approx(fresh[k] - accepted[2][k], abs=1e-3)
        for k in fresh}
    assert bls["submitted"] == bls["offloaded"] + bls["inline"] \
        == len([m for m, _t, _b in ordered if m.pp_seq_no <= 7])
    assert bls["depth_at_new_view"] == 0 and bls["start_join_ms"] >= 0
    # and the phase's spans are on the metrics store, once each
    folds = pool.nodes[name].metrics.summary()
    for metric in ("recertify", "first_cut", "first_round", "fresh_order",
                   "bls_join_wait"):
        assert folds[f"consensus.vc_{metric}"]["count"] == 1, metric
    assert folds["consensus.vc_fresh_order"]["sum"] == pytest.approx(
        e["fresh_ordered_ms"] / 1e3)
    assert sum(folds[f"consensus.vc_{m}"]["sum"] for m in (
        "recertify", "first_cut", "first_round")) == pytest.approx(
        e["fresh_ordered_ms"] / 1e3)


def test_with_batches_in_flight_every_survivor_orders_a_cited_one_first(
        episode):
    """The backlog scenario: DEPTH batches were prepared and ordered
    nowhere, so on every survivor the stamp pair of before closes on a
    re-certified batch and the first fresh one, which takes the backlog,
    is ordered later."""
    pool, victim, survivors, at_stop = episode
    for n in survivors:
        e = pool.nodes[n].validator_info()["view_change"]["ordering"]
        _steps_in_order(e)
        assert e["first_ordered_kind"] == "recertified", (n, e)
        assert e["first_ordered_requests"] == BATCH
        assert e["cited_reapplied"] == e["cited_ordered"] == DEPTH
        assert e["cited_reapplied"] + e["cited_recertified_only"] \
            == e["reordered_batches"]
        assert e["first_ordered_ms"] < e["fresh_ordered_ms"], (n, e)
        assert e["bls"]["submitted"] == DEPTH + 1


def test_the_first_fresh_pre_prepare_carries_the_multi_sig_before_it(
        recertified):
    """The batch before it was ordered in view 0 and only re-certified in
    view 1: the view-0 aggregate over its state root is the signature."""
    from plenum_tpu.crypto.multi_signature import MultiSignature
    pool, survivors, seen = recertified
    assert len({id(pp) for pp in seen["fresh"]}) == 1
    pp = seen["fresh"][0]
    assert pp.original_view_no in (None, 1)
    ms = MultiSignature.from_list(list(pp.bls_multi_sig))
    assert ms.value.state_root_hash == seen["roots"][6]
    for n in ("Gamma", "Delta"):
        assert pool.nodes[n].master_replica.bls.validate_pre_prepare(
            pp, "Beta") is None
    assert {_domain(pool.nodes[n]).size for n in survivors} == {1 + 32}


def test_what_was_kept_goes_with_the_stable_checkpoint(recertified):
    """Read two batches past checkpoints 10, 20 and 30: what a node keeps
    of its BLS work is what lies past its stable checkpoint, the same
    size each time, so it does not grow with the ledger."""
    pool, survivors, seen = recertified
    for n in survivors:
        assert seen["kept_before_gc"][n] == 7, n
        readings = seen["kept_after_gc"][n]
        assert [r[0] for r in readings] == [10, 20, 30], n
        for stable, own, verified in readings:
            assert own == [stable + 1, stable + 2], (n, stable, own)
            # the multi-sig of the checkpoint's last batch stays: the
            # PRE-PREPARE after it carried it
            assert verified and verified[0] >= stable, (n, verified)
            # two a batch past the checkpoint (the one aggregated, the
            # one the next PRE-PREPARE carried) and the checkpoint's own
            assert len(verified) <= 2 * 2 + 1, (n, verified)
        bls = pool.nodes[n].master_replica.bls
        assert all(key > (1, 30) for key in bls._sigs), (n, list(bls._sigs))


def test_a_node_that_holds_no_signature_signs_afresh():
    """What a node keeps of its BLS work lives in memory and goes with a
    restart. Gamma's is emptied as a restart leaves it (a node really
    started again also lacks the bodies of the requests it committed in
    its first life, stashes the re-sent PRE-PREPAREs as MISSING_REQUESTS
    and casts no vote on them at all): it re-certifies with signatures it
    makes anew, the same bytes (sk * H(value)) it had sent in view 0."""
    pool = Pool(tracing=False, config=Config(
        Max3PCBatchWait=0.05, Max3PCBatchSize=1,
        PRIMARY_DISCONNECT_TIMEOUT=1.5, ORDERING_PROGRESS_TIMEOUT=300.0,
        STATE_FRESHNESS_UPDATE_INTERVAL=300.0))
    survivors = ["Beta", "Gamma", "Delta"]
    wire: list = []
    pool.net.add_rule(Discard(), lambda m, frm, dst: isinstance(m, Commit)
                      and m.inst_id == 0 and frm == "Gamma"
                      and wire.append(m) and False)
    for rid in range(1, 4):
        pool.submit(signed_nym(pool.trustee, _user(8000 + rid), rid))
        pool.run(0.5)
    pool.run(2.0)
    view0 = {m.pp_seq_no: m.bls_sig for m in wire}
    assert sorted(view0) == [1, 2, 3]

    gamma = pool.nodes["Gamma"]
    assert len(gamma.master_replica.bls._own_sigs) == 3
    gamma.master_replica.bls._own_sigs.clear()
    signed: list = []
    _spy(gamma.c.bls_signer, "sign", signed)
    pool.crash_node("Alpha")
    del wire[:]
    pool.run(8.0)
    episodes = {n: pool.nodes[n].validator_info()["view_change"]["ordering"]
                for n in survivors}
    assert {e["reordered_batches"] for e in episodes.values()} == {3}
    assert [(episodes[n]["bls_sigs_reused"], episodes[n]["bls_sigs_fresh"])
            for n in survivors] == [(3, 0), (0, 3), (3, 0)]
    assert len(signed) == 3
    assert {m.pp_seq_no: m.bls_sig for m in wire if m.view_no == 1} == view0
    pool.submit(signed_nym(pool.trustee, _user(8004), 4), to=survivors)
    pool.run(4.0)
    assert {_domain(pool.nodes[n]).size for n in survivors} == {5}


def test_a_re_certified_batch_reaches_process_order_only_where_it_is_new(
        recertified):
    """A batch at or below a survivor's `last_ordered` has its new-view
    quorum parked by the in-order rule and never comes to `_order` again:
    no aggregate, no store `put`, nothing to skip. Only Delta, which had
    not ordered batches 5 and 6, runs the order-time check (`submit_order`,
    which `_order` calls since PR 48), for those two."""
    pool, survivors, seen = recertified
    assert seen["aggregated"] == {"Beta": [], "Gamma": [],
                                  "Delta": [(1, 5), (1, 6)]}


def test_a_pre_prepares_content_is_digested_once_an_object(monkeypatch):
    import dataclasses
    from plenum_tpu.consensus.ordering_service import OrderingService
    pool = Pool(tracing=False)
    pool.submit(signed_nym(pool.trustee, _user(9100), 1))
    pool.run(2.0)
    pp = pool.nodes["Beta"].master_replica.ordering.prePrepares[(0, 1)]
    fresh = dataclasses.replace(pp)                 # as off the wire
    digested: list = []
    real = OrderingService._batch_digest
    monkeypatch.setattr(OrderingService, "_batch_digest", staticmethod(
        lambda m: digested.append(1) or real(m)))
    assert [OrderingService._content_digest(fresh) for _ in range(4)] \
        == [pp.digest] * 4
    assert len(digested) == 1
    # another object, another check: a changed batch does not ride on the
    # digest of the one it was made from
    forged = dataclasses.replace(fresh, req_idr=fresh.req_idr + ("ff" * 32,))
    assert OrderingService._content_digest(forged) != forged.digest
    assert len(digested) == 2


def test_a_view_changes_start_is_on_disk_when_the_view_change_starts(
        tmp_path):
    """The flight recorder's dump of `view_change_start` is written in
    line, before anything that follows can lose it, and ends with the
    anomaly that took it."""
    import json
    pool = Pool(config=Config(Max3PCBatchWait=0.05,
                              PRIMARY_DISCONNECT_TIMEOUT=1.5,
                              ORDERING_PROGRESS_TIMEOUT=300.0,
                              STATE_FRESHNESS_UPDATE_INTERVAL=300.0))
    node = pool.nodes["Gamma"]
    node.tracer.dump_dir = str(tmp_path)
    pool.submit(signed_nym(pool.trustee, _user(9200), 1))
    pool.run(3.0)
    pool.crash_node("Alpha")
    for _ in range(40):
        pool.run(0.1)
        if node.validator_info()["view_change"]["started"]:
            break
    assert node.validator_info()["view_change"]["started"] == 1
    dumps = list(tmp_path.glob("Gamma-flight-*.json"))
    assert len(dumps) == 1
    events = json.loads(dumps[0].read_text())["events"]
    assert [e[1] for e in events].count("anomaly.view_change_start") == 1


def test_a_request_finalised_after_it_was_executed_is_counted_and_queued():
    """What separates the slow failover runs from the fast ones on the chip
    (PERF.md section 6, PR 49): ordering needs a request's BODY, not its
    propagate quorum. A node whose peers' PROPAGATEs reach it late orders
    and commits the batch on the body it has, sees the quorum afterwards
    and queues the request for ordering all the same. `propagation.
    forwarded_after_executed` counts exactly what then sits in its queues
    for a node that becomes primary to propose again."""
    from plenum_tpu.common.node_messages import Propagate, PropagateBatch
    from plenum_tpu.network import Stash
    pool = Pool(tracing=False)
    late = pool.net.add_rule(Stash(), lambda m, frm, dst: dst == "Delta"
                             and isinstance(m, (Propagate, PropagateBatch)))
    for i in range(3):
        pool.submit(signed_nym(pool.trustee, _user(9300 + i), i + 1))
    pool.run(3.0)
    delta = pool.nodes["Delta"]
    assert _domain(delta).size == 4         # ordered on the bodies it holds
    assert delta.validator_info()["propagation"] == {
        "forwarded_after_executed": 0}
    pool.net.remove_rule(late)              # the quorum, after the commit
    pool.run(1.0)
    queued = [d for q in delta.master_replica.ordering.request_queues
              .values() for d in q]
    assert 1 <= len(queued) <= 3
    assert all(delta.propagator.requests[d].executed for d in queued)
    assert delta.validator_info()["propagation"] == {
        "forwarded_after_executed": len(queued)}
    for n in ("Alpha", "Beta", "Gamma"):    # on time: nothing left queued
        assert pool.nodes[n].propagator.stats["forwarded_after_executed"] == 0
        assert not any(pool.nodes[n].master_replica.ordering
                       .request_queues.values())
