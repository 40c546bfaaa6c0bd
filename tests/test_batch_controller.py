"""Closed-loop batch controller + deep-pipeline ordering tests.

Controller determinism: every sample the controller sees is stamped on the
injectable timer and every decision is a pure function of those samples —
these tests drive MockTimer and assert exact knob movements (no wall-clock
reads anywhere in the control path). The pipeline tests use the PoolSim
service harness from test_consensus.
"""
import pytest

from plenum_tpu.common.internal_messages import ViewChangeStarted
from plenum_tpu.common.node_messages import Checkpoint, Commit
from plenum_tpu.common.timer import MockTimer
from plenum_tpu.common import tracing
from plenum_tpu.config import Config
from plenum_tpu.consensus.batch_controller import (BatchController,
                                                   make_controller)
from plenum_tpu.network import Discard, Stash, match_type

from test_consensus import NODES, PoolSim, make_request


def make_ctl(timer=None, **overrides) -> BatchController:
    cfg = Config(**overrides)
    return BatchController(cfg, timer or MockTimer())


# --- controller policy (pure, deterministic) -------------------------------


def test_idle_tick_holds_every_knob():
    ctl = make_ctl()
    before = (ctl.batch_size, ctl.batch_wait, ctl.depth,
              ctl.group_commit_max)
    ctl.tick()
    assert (ctl.batch_size, ctl.batch_wait, ctl.depth,
            ctl.group_commit_max) == before
    assert ctl.decisions == 0


def test_queueing_dominated_shrinks_wait_and_full_batches():
    """SLO violated with queue wait the largest stage: requests spend
    their latency WAITING — the wait shrinks multiplicatively, and the
    batch size too when batches are being cut full."""
    ctl = make_ctl(Max3PCBatchWait=0.1, BATCH_SLO_P95=0.2)
    for _ in range(20):
        ctl.note_batch_cut(queue_wait=0.5, n_reqs=ctl.batch_size)  # full
        ctl.note_ordered(0.01)
    size0, wait0 = ctl.batch_size, ctl.batch_wait
    ctl.tick()
    assert ctl.last_decision["verdict"] == "shrink:queueing"
    assert ctl.batch_wait == pytest.approx(wait0 * 0.5)
    assert ctl.batch_size < size0
    # repeated pressure floors at the configured bounds, never below
    for _ in range(40):
        for _ in range(4):
            ctl.note_batch_cut(0.5, ctl.batch_size)
            ctl.note_ordered(0.01)
        ctl.tick()
    assert ctl.batch_wait == pytest.approx(Config().BATCH_WAIT_MIN)
    assert ctl.batch_size == Config().BATCH_SIZE_MIN


def test_fixed_cost_dominated_grows_wait_and_coalescing():
    """SLO violated, batches underfull, 3PC span dominant: per-batch fixed
    costs are being paid on near-empty batches — the wait GROWS so more
    requests coalesce per batch (sim25's shape: tiny batches, n-squared
    vote flood per batch)."""
    ctl = make_ctl(Max3PCBatchWait=0.05, BATCH_SLO_P95=0.2,
                   GROUP_COMMIT_MAX_BATCHES=32)
    assert ctl.group_commit_max == 8      # starts below the cap (room to act)
    for _ in range(20):
        ctl.note_batch_cut(queue_wait=0.01, n_reqs=30)   # 3% full
        ctl.note_ordered(0.5)                            # costly 3PC
    wait0, coal0 = ctl.batch_wait, ctl.group_commit_max
    ctl.tick()
    assert ctl.last_decision["verdict"] == "grow:fixed-cost"
    assert ctl.batch_wait == pytest.approx(wait0 * 1.5)
    assert ctl.group_commit_max == coal0 + 4
    # and it caps at BATCH_WAIT_MAX under sustained pressure
    for _ in range(40):
        for _ in range(4):
            ctl.note_batch_cut(0.01, 30)
            ctl.note_ordered(0.5)
        ctl.tick()
    assert ctl.batch_wait == pytest.approx(Config().BATCH_WAIT_MAX)


def test_saturated_full_batches_shrink_depth():
    """SLO violated with FULL batches and service-side spans dominant:
    genuinely too much in flight — the speculative window backs off."""
    ctl = make_ctl(BATCH_SLO_P95=0.2)
    depth0 = ctl.depth
    for _ in range(20):
        ctl.note_batch_cut(queue_wait=0.01, n_reqs=ctl.batch_size)
        ctl.note_ordered(0.5)
    ctl.tick()
    assert ctl.last_decision["verdict"] == "shrink:depth"
    assert ctl.depth == int(depth0 * 0.7)
    # floors at the legacy window of 4, never a dead pipeline
    for _ in range(40):
        for _ in range(4):
            ctl.note_batch_cut(0.01, ctl.batch_size)
            ctl.note_ordered(0.5)
        ctl.tick()
    assert ctl.depth == 4


def test_headroom_deepens_and_decays_grown_wait():
    ctl = make_ctl(Max3PCBatchWait=0.05, BATCH_SLO_P95=0.5,
                   Max3PCBatchesInFlight=64)
    ctl.depth = 10
    ctl.batch_wait = 0.4                   # left high by a past episode
    ctl.group_commit_max = 20              # ditto
    for _ in range(10):
        ctl.note_batch_cut(queue_wait=0.001, n_reqs=ctl.batch_size)
        ctl.note_ordered(0.005)
    size0 = ctl.batch_size
    ctl.tick()
    assert ctl.last_decision["verdict"] == "grow:headroom"
    assert ctl.depth == 11                 # additive increase
    assert ctl.batch_size == size0         # already at the config cap
    assert ctl.batch_wait == pytest.approx(0.4 * 0.9)
    assert ctl.group_commit_max == 19      # decays toward its start value


def test_load_shift_moves_knobs_in_expected_direction():
    """The acceptance shape: a deterministic load shift on the injectable
    timer moves the chosen knobs the expected way — light load grows the
    window, a queue-wait storm shrinks wait/size, and recovery grows the
    window again."""
    timer = MockTimer()
    cfg = Config(Max3PCBatchWait=0.05, BATCH_SLO_P95=0.2,
                 BATCH_CONTROL_INTERVAL=0.5)
    ctl = BatchController(cfg, timer)
    ctl.depth = 8

    def feed(n, wait, fill, span):
        for _ in range(n):
            ctl.note_batch_cut(wait, fill)
            ctl.note_ordered(span)
        timer.advance(0.5)
        ctl.note_ordered(span)    # first sample past the deadline decides

    feed(10, wait=0.001, fill=ctl.batch_size, span=0.01)   # light
    assert ctl.depth == 9
    depth_light = ctl.depth
    size_light = ctl.batch_size
    for _ in range(3):                                     # overload
        feed(10, wait=0.6, fill=ctl.batch_size, span=0.01)
    assert ctl.batch_wait < 0.05 and ctl.batch_size < size_light
    feed(10, wait=0.001, fill=ctl.batch_size, span=0.01)   # recovery
    assert ctl.depth == depth_light + 1
    assert ctl.decisions == 5


def test_decisions_ride_the_tracer():
    timer = MockTimer()
    tracer = tracing.Tracer("N", timer.get_current_time)
    ctl = BatchController(Config(BATCH_SLO_P95=0.2), timer, tracer=tracer)
    ctl.note_batch_cut(0.5, ctl.batch_size)
    ctl.note_ordered(0.01)
    ctl.tick()
    events = [e for e in tracer.ring if e[1] == tracing.CONTROLLER]
    assert len(events) == 1
    assert events[0][3]["verdict"] == "shrink:queueing"
    assert events[0][3]["slo_ms"] == 200.0


def test_make_controller_config_gate():
    assert make_controller(Config(BATCH_CONTROLLER=False), MockTimer()) is None
    assert make_controller(Config(), MockTimer()) is not None


# --- satellite regression: the leftover-queue wait clock -------------------


def test_partial_batch_wait_clock_survives_inflight_backpressure():
    """Regression: send_3pc_batch used to re-arm the per-ledger wait clock
    on every prod tick that left a leftover queue — so while the in-flight
    gate held fresh cuts back, a queued partial batch's Max3PCBatchWait
    restarted every tick, and after the gate opened it still waited one
    FULL extra period. The enqueue stamp now rides the queue entry itself:
    once capacity frees, a request that has already waited out the bound
    is cut on the next service pass."""
    pool = PoolSim(config=Config(Max3PCBatchWait=1.0,
                                 Max3PCBatchesInFlight=1,
                                 BATCH_CONTROLLER=False))
    pool.net.set_latency(0.001, 0.01)     # keep delivery ≪ the batch wait
    primary = pool.primary_name()
    ordering = pool.replicas[primary].ordering
    # batch 1 occupies the whole in-flight window (commits stashed)
    rule = pool.net.add_rule(Stash(), match_type(Commit))
    pool.finalize_request(make_request(0))
    pool.run(1.5)
    assert pool.replicas[primary].data.pp_seq_no == 1
    assert not pool.ordered[primary]
    # a second request arrives and waits OUT its full bound behind the gate
    pool.finalize_request(make_request(1))
    pool.run(2.0)
    assert pool.replicas[primary].data.pp_seq_no == 1   # gate held
    # heal: stashed commits deliver, batch 1 orders, the gate opens —
    # the overdue partial batch must cut on the next service pass, NOT
    # after another full Max3PCBatchWait
    pool.net.remove_rule(rule)
    pool.run(0.5, step=0.25)
    assert pool.replicas[primary].data.pp_seq_no == 2, \
        "overdue partial batch waited a fresh full period after the " \
        "in-flight gate opened (wait clock was re-armed)"


# --- deep pipeline ---------------------------------------------------------


def test_deep_window_pins_at_high_watermark_and_resumes():
    """Speculative cuts run to the high watermark and STOP (the protocol
    bound); once checkpoints stabilize and the window slides, the backlog
    drains. LOG_SIZE=4 with CHK_FREQ=2 so the boundary is cheap to hit."""
    pool = PoolSim(config=Config(Max3PCBatchSize=1, Max3PCBatchWait=0.0,
                                 CHK_FREQ=2, LOG_SIZE=4,
                                 BATCH_CONTROLLER=False,
                                 Max3PCBatchesInFlight=300))
    primary = pool.primary_name()
    # hold checkpoint traffic: the watermark window cannot slide
    rule = pool.net.add_rule(Stash(), match_type(Checkpoint))
    for i in range(10):
        pool.finalize_request(make_request(i))
    pool.run(5.0)
    data = pool.replicas[primary].data
    assert data.pp_seq_no == data.high_watermark == 4, \
        f"primary ran past the watermark window: {data.pp_seq_no}"
    assert sum(len(q) for q in
               pool.replicas[primary].ordering.request_queues.values()) == 6
    # heal: checkpoints stabilize, the window slides, the backlog drains
    pool.net.remove_rule(rule)
    pool.run(8.0)
    for name in NODES:
        assert [o.pp_seq_no for o in pool.ordered[name]] == list(range(1, 11))


def _slow_commit_cut_depth(depth: int) -> tuple[int, int]:
    """-> (pp_seq_no cut, batches ordered) at a fixed sim time, with every
    COMMIT delayed 1.0 s and a steady request trickle."""
    pool = PoolSim(config=Config(Max3PCBatchSize=1, Max3PCBatchWait=0.0,
                                 BATCH_CONTROLLER=False,
                                 Max3PCBatchesInFlight=depth))
    pool.net.set_latency(0.001, 0.002)
    from plenum_tpu.network import Deliver
    pool.net.add_rule(Deliver(1.0, 1.0), match_type(Commit))
    primary = pool.primary_name()
    for i in range(30):
        pool.finalize_request(make_request(i))
        pool.run(0.05, step=0.05)
    pool.run(0.5, step=0.05)
    return (pool.replicas[primary].data.pp_seq_no,
            len(pool.ordered[primary]))


def test_deep_window_decouples_cuts_from_slow_commits():
    """The tentpole's core claim, deterministically: with COMMITs slowed to
    1 s, the legacy 4-deep window stalls every fresh cut behind the oldest
    uncommitted batch, while the deep window keeps cutting speculative
    batches — same pool, same trickle, same sim clock."""
    deep_cut, deep_ordered = _slow_commit_cut_depth(64)
    legacy_cut, legacy_ordered = _slow_commit_cut_depth(4)
    assert legacy_cut <= legacy_ordered + 4     # the old hard ceiling
    assert deep_cut >= legacy_cut * 2, \
        f"deep window cut only {deep_cut} vs legacy {legacy_cut}"
    assert deep_ordered >= legacy_ordered


def test_view_change_reverts_deep_speculative_stack_in_reverse():
    """N>4 speculative uncommitted applies revert in EXACT reverse apply
    order on a view change (the deep-pipeline extension of the reference's
    _revert contract)."""
    n_batches = 7
    pool = PoolSim(config=Config(Max3PCBatchSize=1, Max3PCBatchWait=0.0,
                                 Max3PCBatchesInFlight=300))
    primary = pool.primary_name()
    executor = pool.executors[primary]
    rule = pool.net.add_rule(Discard(), match_type(Commit))
    for i in range(n_batches):
        pool.finalize_request(make_request(i))
    pool.run(3.0)
    applied = list(executor.applied)
    assert len(applied) == n_batches > 4
    reverted = []
    original = executor.revert_last_batch

    def spying_revert(ledger_id):
        reverted.append(executor.applied[-1])
        original(ledger_id)

    executor.revert_last_batch = spying_revert
    pool.replicas[primary].ordering.process_view_change_started(
        ViewChangeStarted(view_no=1))
    assert reverted == list(reversed(applied))
    assert executor.applied == []
    pool.net.remove_rule(rule)


# --- the self-clocked wait gate (OrderingService._cut_reason) ---------------
# A partial batch waits only while an earlier batch of the instance is still
# being ordered, and never past the batch wait. Every cut names its reason.


def _gate_pool(**overrides):
    """PoolSim with a batch wait long enough that only the gate's other
    arms can cut inside a test -> (pool, primary's data, its ordering)."""
    cfg = dict(Max3PCBatchWait=10.0, BATCH_CONTROLLER=False)
    cfg.update(overrides)
    pool = PoolSim(config=Config(**cfg))
    pool.net.set_latency(0.001, 0.01)
    primary = pool.replicas[pool.primary_name()]
    return pool, primary.data, primary.ordering


def _cuts(**nonzero):
    return {"full": 0, "idle": 0, "timeout": 0, "forced": 0, **nonzero}


def test_gate_idle_instance_cuts_a_queued_request_at_once():
    pool, data, ordering = _gate_pool()
    pool.finalize_request(make_request(0))
    ordering.service()            # no time has passed: the wait bought nothing
    assert data.pp_seq_no == 1
    assert ordering.cuts == _cuts(idle=1)


def test_gate_holds_behind_inflight_batch_then_cuts_the_queue_whole():
    pool, data, ordering = _gate_pool()
    rule = pool.net.add_rule(Stash(), match_type(Commit))
    pool.finalize_request(make_request(0))
    pool.run(0.5)
    assert data.pp_seq_no == 1 and data.last_ordered_3pc[1] == 0
    later = [make_request(i) for i in (1, 2, 3)]
    for req in later:
        pool.finalize_request(req)
        pool.run(0.5)
    assert data.pp_seq_no == 1, "partial queue cut behind a batch in flight"
    assert len(ordering.request_queues[1]) == 3
    # batch 1 orders: the turn after finds the instance idle and proposes
    # everything that queued meanwhile as ONE batch
    pool.net.remove_rule(rule)
    pool.run(0.5)
    assert data.pp_seq_no == 2
    assert ordering.sent_preprepares[(0, 2)].req_idr == tuple(
        r.digest for r in later)
    assert ordering.cuts == _cuts(idle=2)
    for name in NODES:
        assert [o.pp_seq_no for o in pool.ordered[name]] == [1, 2]


def test_gate_inflight_and_never_ordered_cuts_at_the_batch_wait_not_later():
    pool, data, ordering = _gate_pool(Max3PCBatchWait=1.0)
    pool.net.add_rule(Discard(), match_type(Commit))
    pool.finalize_request(make_request(0))
    pool.run(0.5)
    assert data.pp_seq_no == 1
    pool.finalize_request(make_request(1))
    pool.run(1.0)                 # the last service() saw it 0.75 s old
    assert data.pp_seq_no == 1
    ordering.service()            # exactly batch_wait old
    assert data.pp_seq_no == 2
    assert ordering.cuts == _cuts(idle=1, timeout=1)


def test_gate_full_queue_cuts_whatever_is_in_flight():
    pool, data, ordering = _gate_pool(Max3PCBatchSize=3)
    pool.net.add_rule(Stash(), match_type(Commit))
    pool.finalize_request(make_request(0))
    pool.run(0.5)
    for i in range(1, 8):         # 7 queued behind batch 1: 3 + 3 + 1
        pool.finalize_request(make_request(i))
    ordering.service()
    assert data.pp_seq_no == 3
    assert ordering.cuts == _cuts(idle=1, full=2)
    # the odd one out is a partial batch like any other: it holds
    assert len(ordering.request_queues[1]) == 1


def _block_reproposal(ordering, data):
    ordering._awaiting_reproposal.add("digest-the-new-primary-lacks")
    return lambda: ordering._awaiting_reproposal.clear()


def _block_old_view(ordering, data):
    ordering._awaited_old_view[(0, 1)] = "cited-digest"
    return lambda: ordering._awaited_old_view.clear()


def _block_new_view(ordering, data):
    data.waiting_for_new_view = True

    def release():
        data.waiting_for_new_view = False
    return release


@pytest.mark.parametrize("block", [_block_reproposal, _block_old_view,
                                   _block_new_view])
def test_gate_idle_rule_stays_behind_the_view_change_guards(block):
    pool, data, ordering = _gate_pool()
    release = block(ordering, data)
    pool.finalize_request(make_request(0))
    pool.run(1.0)
    assert data.pp_seq_no == 0 and not any(ordering.cuts.values())
    release()
    ordering.service()
    assert data.pp_seq_no == 1 and ordering.cuts == _cuts(idle=1)


def test_gate_bodyless_head_keeps_its_retry_cadence():
    """An idle instance whose whole queue awaits request bodies must not
    spin: the re-queue's fresh stamp paces the pull to one per batch wait."""
    pool, data, ordering = _gate_pool(Max3PCBatchWait=1.0)
    pulls = []
    from plenum_tpu.common.internal_messages import RequestPropagates
    pool.replicas[pool.primary_name()].internal_bus.subscribe(
        RequestPropagates, pulls.append)
    req = make_request(0)
    pool.finalize_request(req)
    del pool.requests[req.digest]          # the body never reached us
    pool.run(2.0)
    assert data.pp_seq_no == 0
    assert 1 <= len(pulls) <= 2
    pool.requests[req.digest] = req        # it lands: cut on the next turn
    ordering.service()
    assert data.pp_seq_no == 1


def test_gate_cut_reasons_add_up_to_the_batches_cut():
    """All four reasons in one run."""
    pool = PoolSim(config=Config(Max3PCBatchSize=3, Max3PCBatchWait=1.0,
                                 STATE_FRESHNESS_UPDATE_INTERVAL=5.0))
    pool.net.set_latency(0.001, 0.01)
    primary = pool.replicas[pool.primary_name()]
    rule = pool.net.add_rule(Stash(), match_type(Commit))
    pool.finalize_request(make_request(0))           # idle
    pool.run(0.5)
    for i in (1, 2, 3):
        pool.finalize_request(make_request(i))       # full
    pool.run(0.25)
    pool.finalize_request(make_request(4))           # held, then timeout
    pool.run(2.5)
    pool.net.remove_rule(rule)
    pool.run(8.0)                                    # quiet: freshness
    cuts = primary.ordering.cuts
    assert all(cuts[reason] >= 1 for reason in cuts), cuts
    assert sum(cuts.values()) == primary.data.pp_seq_no
    for name in NODES:
        assert len(pool.ordered[name]) == primary.data.pp_seq_no


def test_gate_self_clocks_under_a_steady_trickle_and_replays_identically():
    """A 4-node pool, every message 20 ms on the wire (so a 3PC round is
    ~60 ms), one write every 10 ms and a batch wait nothing reaches: the
    primary proposes what queued during its own last round, so batches
    hold several requests with no timer involved. The decision reads only
    consensus state and the injectable timer, so replaying the primary's
    recorded inputs cuts the same batches: byte-identical spans."""
    from plenum_tpu.common.event_bus import ExternalBus
    from plenum_tpu.common.metrics import MetricsName
    from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID
    from plenum_tpu.common.tracing import Tracer, span_sequence
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.network import SimNetwork, SimRandom
    from plenum_tpu.node import Node, NodeBootstrap
    from plenum_tpu.node.recorder import Recorder, attach_recorder, replay
    from plenum_tpu.storage.kv_memory import KvMemory
    from test_pool import NODES as POOL_NODES, make_genesis, signed_nym

    n_writes = 60
    genesis, trustee = make_genesis(POOL_NODES)
    timer = MockTimer()
    net = SimNetwork(timer, SimRandom(11))
    net.set_latency(0.02, 0.02)
    config = Config(Max3PCBatchWait=5.0)
    recorder = Recorder(KvMemory(), now=timer.get_current_time)
    primary = "Alpha"
    nodes = {}
    for name in POOL_NODES:
        components = NodeBootstrap(name, genesis_txns=genesis).build()
        tracer = Tracer(name, timer.get_current_time,
                        wall_durations=False) if name == primary else None
        nodes[name] = Node(name, timer, net.create_peer(name), components,
                           config=config, tracer=tracer)
    assert nodes[primary].replicas[0].data.is_primary
    attach_recorder(nodes[primary], recorder)
    net.connect_all()

    for i in range(n_writes + 100):
        if i < n_writes:
            user = Ed25519Signer(seed=(b"trickle-%d" % i).ljust(32, b"\0"))
            req = signed_nym(trustee, user, i + 1)
            for node in nodes.values():
                node.handle_client_message(req.to_dict(), "cli")
        for node in nodes.values():
            node.prod()
        timer.advance(0.01)

    ledgers = [n.c.db.get_ledger(DOMAIN_LEDGER_ID) for n in nodes.values()]
    assert {lg.size for lg in ledgers} == {1 + n_writes}
    assert len({lg.root_hash for lg in ledgers}) == 1
    master = nodes[primary].replicas[0]
    cuts = master.ordering.cuts
    assert cuts["timeout"] == 0 and cuts["full"] == 0, cuts
    assert cuts["idle"] == master.data.pp_seq_no
    assert 3 <= n_writes / cuts["idle"], \
        f"{cuts['idle']} batches for {n_writes} writes: not self-clocked"
    assert nodes[primary].validator_info()[
        "batch_controller"]["cuts"] == cuts
    assert nodes[primary].metrics.summary()[
        MetricsName.BATCH_CUT_IDLE]["max"] == cuts["idle"]

    live = span_sequence(nodes[primary].tracer.snapshot())
    first_ts = next(ts for ts, *_ in recorder.iter_records())
    timer2 = MockTimer(start=first_ts)
    tracer2 = Tracer(primary, timer2.get_current_time, wall_durations=False)
    node2 = Node(primary, timer2,
                 ExternalBus(send_handler=lambda msg, dst: None),
                 NodeBootstrap(primary, genesis_txns=genesis).build(),
                 config=config, tracer=tracer2)
    replay(recorder.iter_records(), node2, timer2)
    assert node2.replicas[0].ordering.cuts == cuts
    assert span_sequence(tracer2.snapshot()) == live
