"""Tracing plane: span events, flight recorder, waterfall assembly,
NullTracer disabled-cost budget, and the metrics satellites this PR
shipped with it (nearest-rank percentile fix, deterministic reservoir
sampling).
"""
from __future__ import annotations

import json
import time

import pytest

from plenum_tpu.common.metrics import SAMPLE_CAP, Accumulator, percentile
from plenum_tpu.common.node_messages import Reply
from plenum_tpu.common.tracing import NULL_TRACER, Tracer, span_sequence
from plenum_tpu.crypto.ed25519 import Ed25519Signer
from plenum_tpu.tools.trace_report import (assemble, attribution_summary,
                                           summarize)

from plenum_tpu.config import Config
from test_pool import Pool, signed_nym


# --- metrics satellites -----------------------------------------------------

def test_percentile_nearest_rank_pins():
    """Nearest-rank: rank = ceil(q*n); the old int(q*n) sat one rank high
    for every integral q*n (p50 of 4 values returned the 3rd)."""
    assert percentile([1, 2, 3, 4], 0.5) == 2
    assert percentile([1, 2, 3, 4], 0.25) == 1
    assert percentile([1, 2, 3, 4], 0.75) == 3
    assert percentile([1, 2, 3, 4], 1.0) == 4
    assert percentile([1, 2, 3, 4], 0.0) == 1
    assert percentile([7], 0.95) == 7
    assert percentile(list(range(1, 101)), 0.5) == 50
    assert percentile(list(range(1, 101)), 0.95) == 95
    assert percentile(list(range(1, 101)), 1.0) == 100
    assert percentile([3, 1, 2], 0.5) == 2          # unsorted input
    assert percentile([], 0.5) is None


def test_accumulator_reservoir_is_deterministic_and_unbiased():
    """Samples are a seeded reservoir over the WHOLE interval: the same
    add() sequence reproduces the same set (replay-stable), and events
    past the first SAMPLE_CAP are represented — the old first-N sampling
    kept zero of them, over-weighting cold-start costs in every p95."""
    stream = [float(v) for v in range(SAMPLE_CAP * 4)]
    a1 = Accumulator(keep_samples=True, seed=7)
    a2 = Accumulator(keep_samples=True, seed=7)
    for v in stream:
        a1.add(v)
        a2.add(v)
    assert a1.samples == a2.samples
    assert len(a1.samples) == SAMPLE_CAP
    tail = sum(1 for v in a1.samples if v >= SAMPLE_CAP)
    # uniform reservoir over 4x CAP events: ~75% expected from the tail;
    # first-N sampling would have exactly 0
    assert tail > SAMPLE_CAP // 2, tail
    a3 = Accumulator(keep_samples=True, seed=8)
    for v in stream:
        a3.add(v)
    assert a3.samples != a1.samples                 # seeds decorrelate
    # fold stats unaffected by sampling
    assert a1.count == len(stream) and a1.max == stream[-1]


# --- NullTracer disabled-cost budget ----------------------------------------

def test_null_tracer_disabled_cost_microbench():
    """The acceptance budget: tracing disabled must cost <=2% TPS. Every
    hot-path site is `if tracer.enabled: tracer.emit(...)` with
    NullTracer.enabled a class attribute — measure that exact pattern and
    assert the per-request total (~12 guarded sites fire per ordered txn)
    stays under 2% of a 1 ms/txn budget (the 4-node sim spends 3-5 ms of
    CPU per txn; 1 ms is a conservative floor, so passing here passes the
    bench A/B with margin)."""
    tracer = NULL_TRACER
    assert tracer.enabled is False
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        if tracer.enabled:
            tracer.emit("stage", "key", None)
    per_site = (time.perf_counter() - t0) / n
    sites_per_txn = 12
    budget = 0.02 * 0.001       # 2% of 1 ms
    assert per_site * sites_per_txn < budget, \
        f"{per_site * 1e9:.0f} ns/site x {sites_per_txn} sites " \
        f"exceeds {budget * 1e6:.0f} us/txn"


# --- flight recorder mechanics ----------------------------------------------

def test_flight_recorder_ring_bounds_and_auto_dump(tmp_path):
    clock = {"t": 0.0}
    tr = Tracer("N1", lambda: clock["t"], ring_size=8,
                dump_dir=str(tmp_path), min_dump_interval=5.0)
    for i in range(20):
        tr.emit("stage", f"k{i}")
    assert len(tr.ring) == 8                        # bounded
    tr.anomaly("suspicion", {"code": 1})            # auto-dump fires
    tr.anomaly("suspicion", {"code": 2})            # debounced away
    dumps = sorted(tmp_path.glob("N1-flight-*.json"))
    assert len(dumps) == 1
    clock["t"] = 10.0
    tr.anomaly("suspicion", {"code": 3})            # past the debounce
    assert len(sorted(tmp_path.glob("N1-flight-*.json"))) == 2
    snap = json.loads(dumps[0].read_text())
    assert snap["node"] == "N1"
    assert len(snap["events"]) == 8
    assert snap["events"][-1][1] == "anomaly.suspicion"
    assert snap["anomalies"] == 1                   # at dump time


def test_breaker_transitions_reach_flight_recorder():
    """CircuitBreaker.on_transition (the hook the node installs) lands
    every state change in the ring as an anomaly."""
    from plenum_tpu.parallel.supervisor import CircuitBreaker
    tr = Tracer("N", lambda: 0.0)
    br = CircuitBreaker(fail_threshold=2, cooldown=1.0, now=lambda: 0.0)
    br.on_transition = lambda old, new: tr.anomaly(
        "breaker", {"from": old, "to": new})
    br.record_failure()
    br.record_failure()                             # -> open
    br.to_half_open()
    br.close()
    hops = [(e[3]["from"], e[3]["to"]) for e in tr.ring
            if e[1] == "anomaly.breaker"]
    assert hops == [("closed", "open"), ("open", "half_open"),
                    ("half_open", "closed")]


# --- end-to-end: 4-node sim waterfall ---------------------------------------

def _order_one_traced(pool, req):
    """Submit and run until a Reply lands; -> (t_submit, t_reply) sim
    times measured the way a client would."""
    t0 = pool.timer.get_current_time()
    pool.submit(req)
    for _ in range(1000):
        for node in pool.nodes.values():
            node.prod()
        if any(isinstance(m, Reply)
               for m, _ in pool.client_msgs[pool.names[0]]):
            return t0, pool.timer.get_current_time()
        pool.timer.advance(0.01)
    raise AssertionError("request never ordered")


def test_sim_waterfall_stage_sum_matches_e2e():
    """The tentpole acceptance shape on the deterministic sim: every node
    produces a full per-request waterfall, stage sums telescope to within
    10% of the measured end-to-end latency, and pool-level attribution
    reports p50/p95 for each stage including cross-node network time."""
    pool = Pool()
    user = Ed25519Signer(seed=b"waterfall-user".ljust(32, b"\0"))
    req = signed_nym(pool.trustee, user, 1)
    t_submit, t_reply = _order_one_traced(pool, req)
    e2e = t_reply - t_submit
    assert e2e > 0
    pool.run(3.0)       # let the slower replicas finish their own commits

    report = assemble([pool.nodes[n].tracer.snapshot()
                       for n in pool.names])
    assert req.digest in report["requests"]
    per_node = report["requests"][req.digest]
    assert set(per_node) == set(pool.names)         # every node's view
    for node_name, wf in per_node.items():
        for stage in ("inbox", "auth", "propagate", "queue", "ordering",
                      "durable", "reply"):
            assert stage in wf["stages"], (node_name, wf["stages"])
        # stages telescope: their sum IS the node's ingress->reply span
        assert wf["total"] == pytest.approx(wf["end"] - wf["start"],
                                            abs=1e-9), node_name
    # the node whose client reply defined the measured e2e: stage sum
    # within 10% (+1 prod step of measurement granularity)
    wf = per_node[pool.names[0]]
    assert abs(wf["total"] - e2e) <= 0.1 * e2e + 0.011, (wf["total"], e2e)
    att = attribution_summary(report)
    for stage in ("network", "inbox", "auth", "propagate", "queue",
                  "ordering", "durable", "reply", "apply_wall",
                  "durable_wall"):
        assert stage in att, sorted(att)
        assert att[stage]["p50_ms"] >= 0
        assert att[stage]["p95_ms"] >= att[stage]["p50_ms"]
    # the compact bench-line summary rides the same report
    summary = summarize(report)
    assert summary["requests_traced"] == 1
    # a clamped out-of-order stage (a replica can admit the pre-prepare
    # before its own propagate quorum) may shave the ratio slightly
    assert summary["stage_sum_ratio_p50"] == pytest.approx(1.0, abs=0.02)


def test_anomalies_recorded_across_view_change():
    """A primary blackout's story lands in the flight recorder: VC start
    + completion anomalies on the survivors, and the assembled report's
    anomaly timeline carries them in order."""
    from plenum_tpu.config import Config
    pool = Pool(config=Config(Max3PCBatchWait=0.05,
                              PRIMARY_HEALTH_CHECK_FREQ=0.5,
                              ORDERING_PROGRESS_TIMEOUT=2.0,
                              STATE_FRESHNESS_UPDATE_INTERVAL=3.0,
                              NEW_VIEW_TIMEOUT=4.0))
    from plenum_tpu.network import Discard, match_dst, match_frm
    primary = pool.nodes["Alpha"].master_replica.data.primary_name
    pool.net.add_rule(Discard(), match_dst(primary))
    pool.net.add_rule(Discard(), match_frm(primary))
    user = Ed25519Signer(seed=b"vc-anomaly-user".ljust(32, b"\0"))
    pool.submit(signed_nym(pool.trustee, user, 1),
                to=[n for n in pool.names if n != primary])
    pool.run(25.0)
    survivors = [n for n in pool.names if n != primary]
    assert all(pool.nodes[n].master_replica.view_no >= 1
               for n in survivors)
    report = assemble([pool.nodes[n].tracer.snapshot()
                       for n in survivors])
    kinds = [k for (_t, _n, k, _d) in report["anomalies"]]
    assert "view_change_start" in kinds
    assert "view_change_complete" in kinds
    # completion never precedes the first start in the aligned timeline
    assert kinds.index("view_change_start") \
        < kinds.index("view_change_complete")


# --- tooling smoke (the tier-1 CI satellite) --------------------------------

def test_trace_report_check_smoke(capsys):
    """`trace_report --check` assembles a synthetic two-node fixture with
    skewed wall anchors and asserts alignment + waterfall invariants —
    the tier-1 smoke for the assembly path."""
    from plenum_tpu.tools.trace_report import main
    assert main(["--check"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["check"] == "ok"
    assert not out["problems"]


def test_log_analyzer_ingests_flight_dumps(tmp_path):
    """log_analyzer merges flight-recorder anomaly timelines (wall-
    aligned, deduplicated across a dump series) into its per-view
    report next to the spylog-sourced events."""
    from plenum_tpu.tools.log_analyzer import analyze_node
    node_dir = tmp_path / "Node1"
    node_dir.mkdir()
    rows = [{"t": 100.0, "event": "suspicion", "data": [13, "Beta"]},
            {"t": 101.0, "event": "view_change_complete", "data": 1},
            {"t": 102.0, "event": "executed", "data": [1, 1]}]
    (node_dir / "events.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in rows))
    dump = {"node": "Node1", "clock_domain": "wall", "mono_anchor": 0.0,
            "wall_anchor": 100.0, "dumped_at": 3.0, "anomalies": 2,
            "events": [
                [0.2, "pp_sent", "b" * 8, {"seq": 1, "reqs": []}],
                [0.5, "anomaly.breaker", "",
                 {"from": "closed", "to": "open"}],
                [2.5, "anomaly.catchup", "", None]]}
    (node_dir / "Node1-flight-0.json").write_text(json.dumps(dump))
    # a second overlapping dump (auto-dump cascade) must not double-count
    (node_dir / "Node1-flight-1.json").write_text(json.dumps(dump))

    rep = analyze_node(str(node_dir))
    assert rep["flight_anomalies"] == 2
    assert rep["event_counts"]["flight.breaker"] == 1
    assert rep["event_counts"]["flight.catchup"] == 1
    # wall-aligned: breaker (100.5) falls in the view-0 segment, catchup
    # (102.5) after the view change -> view-1 segment
    assert rep["views"][0]["events"].get("flight.breaker") == 1
    assert rep["views"][1]["events"].get("flight.catchup") == 1


def test_waterfall_out_of_order_points_stay_disjoint():
    """A replica can admit the PRE-PREPARE before its OWN propagate
    quorum completes; the waterfall must not re-count the overlap into
    the ordering stage — stage sums always telescope to the observed
    first->last span (regression: overlapping stages inflated totals
    past end-start and poisoned the 10% acceptance ratio)."""
    req, batch = "r" * 8, "b" * 8
    dump = {"node": "N", "clock_domain": "shared", "mono_anchor": 0.0,
            "wall_anchor": None, "dumped_at": 20.0, "anomalies": 0,
            "events": [
                [1.0, "ingress", req, None],
                [2.0, "auth", req, {"ok": True}],
                # pp arrives at t=3, BEFORE the local quorum at t=5
                [3.0, "pp_recv", batch, {"seq": 1, "reqs": [req]}],
                [5.0, "propagate_quorum", req, {"votes": 2}],
                [10.0, "ordered", batch, {"seq": 1}],
                [11.0, "durable", "", {"seqs": [1]}],
                [13.0, "reply", req, {"seq": 1}]]}
    report = assemble([dump])
    wf = report["requests"][req]["N"]
    assert wf["total"] == pytest.approx(wf["end"] - wf["start"], abs=1e-9)
    assert wf["total"] == pytest.approx(12.0, abs=1e-9)   # 13 - 1
    assert wf["stages"]["queue"] == 0.0                   # clamped
    # ordering starts where the covered prefix ends (t=5), not at pp t=3
    assert wf["stages"]["ordering"] == pytest.approx(5.0, abs=1e-9)


def test_span_sequence_canonical():
    tr = Tracer("N", lambda: 1.5)
    tr.emit("ingress", "d1", {"frm": "cli"})
    a = span_sequence(tr.snapshot())
    b = span_sequence(tr.snapshot())
    assert a == b and b"ingress" in a
    assert span_sequence(None) == b""


# --- the stage clock (tracing.StageClock) -----------------------------------

from plenum_tpu.common.metrics import (MetricsCollector, MetricsName,
                                       NullMetricsCollector)
from plenum_tpu.common.tracing import (NULL_STAGE_CLOCK, STAGES, StageClock,
                                       make_stage_clock)

RESIDENCE = MetricsName.STAGE_RESIDENCE
# the stages a node sees of a request it got from a peer, not a client
RELAYED = (MetricsName.STAGE_PROPAGATE_WAIT, MetricsName.STAGE_QUEUE_WAIT,
           MetricsName.STAGE_ORDERING_WAIT, MetricsName.STAGE_COMMIT_WAIT)


class SteppedClock:
    """perf_counter stand-in: every read is 1/1024 s after the last, so
    every span is exact in binary and sums compare with =="""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0 / 1024
        return self.t


def _stepped_pool(**kwargs):
    pool = Pool(**kwargs)
    clock = SteppedClock()
    for node in pool.nodes.values():
        node.stages._clock = clock
    return pool


def _samples(node) -> dict:
    return {name: list(acc.samples)
            for name, acc in node.metrics.accumulators.items()
            if name.startswith("stage.")}


def _new_samples(node, before: dict) -> dict:
    return {name: got[len(before.get(name, ())):]
            for name, got in _samples(node).items()
            if len(got) > len(before.get(name, ()))}


def _write(pool, req_id: int, **submit):
    user = Ed25519Signer(seed=(b"stage-user-%d" % req_id).ljust(32, b"\0"))
    req = signed_nym(pool.trustee, user, req_id)
    before = {n: _samples(pool.nodes[n]) for n in pool.names}
    pool.submit(req, **submit)
    pool.run(6.0)
    return req, {n: _new_samples(pool.nodes[n], before[n])
                 for n in pool.names}


@pytest.mark.parametrize("entry", [None, "Beta"],
                         ids=["client_to_all", "client_to_one"])
def test_stage_samples_sum_to_residence(entry):
    """Every write's seven waits sum to its residence EXACTLY on a node
    that took it from a client (one perf_counter read a boundary: a second
    read anywhere would show as 1/1024 s). A node that got it by PROPAGATE
    has the four middle stages and no inbox, auth, reply or residence."""
    pool = _stepped_pool()
    entries = pool.names if entry is None else [entry]
    whole = dict.fromkeys(pool.names, 0)
    # a replica can admit the PRE-PREPARE before its own propagate quorum
    # (the primary finalised first): that write has no chain on it, only
    # the stages whose two ends it saw in order
    cut_short = {MetricsName.STAGE_INBOX_WAIT, MetricsName.STAGE_AUTH_WAIT,
                 MetricsName.STAGE_ORDERING_WAIT,
                 MetricsName.STAGE_COMMIT_WAIT}
    for req_id in range(1, 6):
        _, new = _write(pool, req_id, to=entries)
        for name in pool.names:
            got = new[name]
            assert all(len(v) == 1 for v in got.values()), got
            if name not in entries:
                assert set(got) in (set(RELAYED), cut_short - {
                    MetricsName.STAGE_INBOX_WAIT,
                    MetricsName.STAGE_AUTH_WAIT}), (name, sorted(got))
            elif set(got) != cut_short:
                assert set(got) == set(STAGES) | {RESIDENCE}, (name, got)
                assert sum(got[s][0] for s in STAGES) == got[RESIDENCE][0]
                assert got[RESIDENCE][0] > 0
                whole[name] += 1
    assert all(whole[name] >= 3 for name in entries), whole
    for name in pool.names:
        report = pool.nodes[name].stages.report()
        n = whole[name]
        assert report["whole"]["count"] == report[RESIDENCE]["count"] == n
        # the spans each request collected on its way, as the sites
        # measured them, against arrival -> REPLY
        assert report["whole"]["sum_s"] == report[RESIDENCE]["sum_s"]
        assert report[MetricsName.STAGE_ORDERING_WAIT]["count"] == 5
        assert pool.nodes[name].validator_info()["stages"] == report


def test_stage_totals_are_weighted_per_request():
    """A batch-keyed stage adds its span once a request the batch carries
    to count and sum and ONE sample to the reservoir, so the seven waits'
    sums telescope to the residence's sum whatever the batching."""
    pool = _stepped_pool()
    users = [Ed25519Signer(seed=(b"stage-w-%d" % i).ljust(32, b"\0"))
             for i in range(12)]
    for i, user in enumerate(users):
        pool.submit(signed_nym(pool.trustee, user, 100 + i))
    pool.run(8.0)
    node = pool.nodes[pool.names[0]]
    report = node.stages.report()
    assert report[RESIDENCE]["count"] == 12
    ordering = node.metrics.accumulators[MetricsName.STAGE_ORDERING_WAIT]
    assert ordering.count == 12 and len(ordering.samples) < 12   # batched
    assert report[MetricsName.STAGE_ORDERING_WAIT]["count"] == 12
    assert sum(report[s]["sum_s"] for s in STAGES) == pytest.approx(
        report[RESIDENCE]["sum_s"], rel=1e-12)
    assert report["whole"]["sum_s"] == pytest.approx(
        report[RESIDENCE]["sum_s"], rel=1e-12)
    # what the flushed store will carry is the same weighted fold
    for s in STAGES + (RESIDENCE,):
        acc = node.metrics.accumulators[s]
        assert (acc.count, acc.total) == pytest.approx(
            (report[s]["count"], report[s]["sum_s"]))


def test_inbox_hold_behind_an_auth_wave_is_inbox_wait():
    """A request that sits in the client inbox while the previous auth
    wave is out shows the hold in stage.inbox_wait, not in auth_wait (the
    wait PR 38 moved, which the waterfall used to start after)."""
    from test_pool import DeferredVerifier
    pool = Pool()
    alpha = pool.nodes["Alpha"]
    now = {"t": 0.0}
    alpha.stages._clock = lambda: now["t"]
    deferred = DeferredVerifier()
    alpha.c.authenticator.core_authenticator.verifier = deferred
    users = [Ed25519Signer(seed=(b"held-%d" % i).ljust(32, b"\0"))
             for i in range(2)]
    first, second = (signed_nym(pool.trustee, u, 200 + i)
                     for i, u in enumerate(users))

    pool.submit(first, to=["Alpha"])
    alpha.prod()                        # popped at 0; its wave stays out
    assert alpha._auth_inflight is not None
    now["t"] = 1.0
    pool.submit(second, to=["Alpha"])   # arrives at 1, must wait
    for _ in range(3):
        alpha.prod()
    assert len(alpha._client_inbox) == 1
    now["t"] = 5.0
    deferred.released = True
    alpha.prod()                        # first settles, second is popped
    alpha.prod()
    acc = alpha.metrics.accumulators
    assert sorted(acc[MetricsName.STAGE_INBOX_WAIT].samples) == [0.0, 4.0]
    assert sorted(acc[MetricsName.STAGE_AUTH_WAIT].samples) == [0.0, 5.0]
    # the ring's INGRESS event carries the timer time of the append
    ingress = [e for e in alpha.tracer.ring if e[1] == "ingress"]
    assert [e[3]["queued"] for e in ingress] == [e[0] for e in ingress]


def test_no_stage_stamp_outlives_its_request():
    """Per-request stamps ride the propagator's request state and leave
    with it (mark_executed + the TTL sweep); the clock's own two maps are
    empty once every verdict and every batch's replies are out."""
    config = Config(Max3PCBatchWait=0.05, EXECUTED_REQ_RETENTION=5.0,
                    PROPAGATES_PHASE_REQ_TIMEOUT=5.0)
    pool = _stepped_pool(config=config)
    for i in range(8):
        user = Ed25519Signer(seed=(b"swept-%d" % i).ljust(32, b"\0"))
        pool.submit(signed_nym(pool.trustee, user, 300 + i))
    pool.run(6.0)
    for node in pool.nodes.values():
        assert node.stages.report()[RESIDENCE]["count"] == 8
        assert not node.stages._popped and not node.stages._batches
        assert all(s.t_in is None and s.t_mark is None
                   for s in node.propagator.requests.values())
    pool.run(30.0, step=0.5)
    for node in pool.nodes.values():
        node._clean_outdated_reqs()
        assert len(node.propagator.requests) == 0
        assert node.footprint()["request_state_entries"] == 0


def test_null_collector_node_has_no_stage_clock():
    """No collector and no tracer: the shared no-op (one call a site);
    a tracer alone keeps the clock for the ring's sake and adds no sample."""
    assert make_stage_clock(NullMetricsCollector(), NULL_TRACER,
                            time.perf_counter) is NULL_STAGE_CLOCK
    pool = Pool(tracing=False)
    assert all(isinstance(n.stages, StageClock) for n in pool.nodes.values())
    quiet = NullMetricsCollector()
    clock = make_stage_clock(quiet, Tracer("N", time.perf_counter),
                             time.perf_counter)
    clock.ingress("d", "cli", clock.arrived())
    assert clock.auth("d", True) is not None
    assert quiet.accumulators == {}
    assert NULL_STAGE_CLOCK.report() is None and \
        NULL_STAGE_CLOCK.arrived() is None


def _best(fn, n: int, repeats: int = 5) -> float:
    """Seconds a call, the best of `repeats` runs of n calls."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def test_stage_clock_live_cost_microbench():
    """The acceptance budget: the live clock under 10 us a request a node,
    every site of a write paid (batches of 8, under every cell's fill),
    against 1 390 us a request at 720 writes/s. The budget is ISSUE 39's,
    made for a host where one add_event costs ~0.3 us; it is held at 10 us
    up to 0.5 us an add_event and scaled by that cost beyond (this
    sandbox: ~1 us an add_event, ~10 us a request)."""
    from types import SimpleNamespace
    from plenum_tpu.node.propagator import RequestState
    metrics = MetricsCollector()
    clock = StageClock(metrics, NULL_TRACER, time.perf_counter)
    states: dict = {}
    clock.states = states.get
    digests = tuple(f"d{i}" for i in range(8))
    pp = SimpleNamespace(digest="b", view_no=0, pp_seq_no=1, ledger_id=1,
                         req_idr=digests)
    msg = SimpleNamespace(view_no=0, pp_seq_no=1)

    def one_batch():
        for d in digests:
            clock.ingress(d, "cli", clock.arrived())
            entered = clock.auth(d, True)
            state = states[d] = RequestState(None, t_mark=clock.stamp())
            state.t_in, state.t_mark, state.t_sum = entered
            state.finalised = True
            clock.finalised(d, state)
        clock.pp_recv(pp, "P")
        clock.ordered((0, 1), pp, 3)
        clock.durable((msg,), None, 0.0)
        for d in digests:
            clock.replied(d, states[d], msg)
        clock.retired(msg)

    per_request = _best(one_batch, 2_000) / len(digests)
    add_event = _best(lambda: metrics.add_event(
        MetricsName.STAGE_INBOX_WAIT, 0.001), 20_000)
    assert clock.whole[0] == 5 * 2_000 * len(digests)
    assert not clock._popped and not clock._batches
    budget = 10e-6 * max(1.0, add_event / 0.5e-6)
    assert per_request < budget, \
        f"{per_request * 1e6:.2f} us a request against " \
        f"{budget * 1e6:.1f} ({add_event * 1e9:.0f} ns an add_event)"


def test_prod_annotations_cost_and_order_the_same(monkeypatch):
    """The four prod.* host spans cost one check a cycle while no trace is
    held (under 1 us), and a prod cycle that takes the annotated path
    orders what the plain one orders."""
    import jax
    assert not jax.profiler.TraceAnnotation.is_enabled()
    pool = Pool(tracing=False)
    node = pool.nodes[pool.names[0]]
    assert node.c.pipeline is None
    node.propagator.flush_outbox = lambda: None
    for name in ("_service_client_msgs", "_service_propagates",
                 "_service_ordered"):
        monkeypatch.setattr(node, name, lambda: 0)
    monkeypatch.setattr(node.replicas, "service_all", lambda: None)

    def bare():
        node._service_client_msgs()
        node._service_propagates()
        node.replicas.service_all()
        node._service_ordered()
        node.propagator.flush_outbox()

    # prod() over five no-op phases against the five bare calls: what is
    # left is the one is_enabled() check, the work-count arithmetic and
    # the call of prod itself, and has to stay under a microsecond
    extra = _best(node.prod, 20_000) - _best(bare, 20_000)
    # as the stage clock's budget: held at 1 us up to 0.5 us an add_event
    # and scaled by that cost beyond (a loaded or slow test host)
    add_event = _best(lambda: node.metrics.add_event("x", 1.0), 20_000)
    budget = 1e-6 * max(1.0, add_event / 0.5e-6)
    assert extra < budget, f"{extra * 1e9:.0f} ns a cycle"

    def ordered_by(traced: bool):
        monkeypatch.setattr(jax.profiler.TraceAnnotation, "is_enabled",
                            staticmethod(lambda: traced))
        p = Pool(tracing=False)
        for i in range(3):
            user = Ed25519Signer(seed=(b"prod-%d" % i).ljust(32, b"\0"))
            p.submit(signed_nym(p.trustee, user, 400 + i))
        p.run(6.0)
        return [(n, p.nodes[n].master_replica.last_ordered_3pc,
                 p.nodes[n].c.db.get_ledger(1).root_hash.hex())
                for n in p.names]

    assert ordered_by(True) == ordered_by(False)


def test_metrics_lint_places_the_stage_names():
    from plenum_tpu.observability.snapshot import schema_section_of
    from plenum_tpu.tools.metrics_lint import run_lint
    out = run_lint()
    assert out["check"] == "ok", out["problems"]
    for name in STAGES + (RESIDENCE,):
        assert schema_section_of(name) == "commit_path"
    # the nine names nothing wrote are gone
    for gone in ("PROD_TIME", "SIG_BATCH_SIZE", "SIG_BATCH_TIME",
                 "BLS_VERIFY_TIME", "MASTER_3PC_BATCH_TIME",
                 "SIG_BATCH_FILL_TIME", "SIG_DISPATCH_TIME", "NODE_MSGS_IN",
                 "NODE_FRAMES_OUT"):
        assert not hasattr(MetricsName, gone)


def test_probe_names_idle_gaps_by_the_host_spans_over_them(monkeypatch):
    """probes/cell_layers.py `probe_gaps`: each device idle gap of the
    sample with the host spans (prod.*, ring.*, svc.*) that overlap it and
    the share of the gap each covers; gaps as trace_reduce's idle_gaps."""
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "cell_layers", os.path.join(root, "probes", "cell_layers.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    from benchmarks import trace_reduce
    ms = 1_000_000
    dev, mods, host = "/device:TPU:0", "XLA Modules", "/host:CPU"
    events = [
        (host, "python", "bench_trace_window", 0, 100 * ms),
        (dev, mods, "jit_verify_kernel_bytes(1)", 10 * ms, 5 * ms),
        (dev, mods, "jit_verify_kernel_bytes(1)", 65 * ms, 5 * ms),
        (dev, "XLA Ops", "%while.351 = ...", 10 * ms, 5 * ms),
        # the 50 ms gap (15 -> 65): 30 ms of prod.ordered, 10 of
        # prod.replicas, a ring.dispatch at its very end
        (host, "python", "prod.replicas", 15 * ms, 10 * ms),
        (host, "python", "prod.ordered", 25 * ms, 30 * ms),
        (host, "python", "ring.dispatch", 64 * ms, 2 * ms),
        (host, "python", "some.other.span", 15 * ms, 50 * ms),
        # the 30 ms after the last program: the worker waiting for jobs
        (host, "python", "svc.drain", 75 * ms, 40 * ms),
    ]
    monkeypatch.setattr(trace_reduce, "xplane_events", lambda path: events)
    gaps = probe.gaps_by_host_span("unused.xplane.pb")
    assert [g["gap_ms"] for g in gaps] == [50.0, 30.0, 10.0]
    assert gaps[0]["before"] == "before jit_verify_kernel_bytes"
    assert gaps[0]["host"] == {"prod.ordered": 0.6, "prod.replicas": 0.2,
                               "ring.dispatch": 0.02}
    assert gaps[1] == {"gap_ms": 30.0, "before": "after the last program",
                       "host": {"svc.drain": 0.8333}}
    assert gaps[2]["host"] == {}
    # as trace_reduce reads the same sample
    reduced = trace_reduce.reduce(events)
    assert [round(g[1] * 1e3, 3) for g in reduced["idle_gaps"]] == [
        50.0, 30.0, 10.0]
    assert reduced["busy_s"] == 0.005       # the host spans moved nothing


def test_a_dump_is_the_snapshot_as_json_dump_would_write_it(tmp_path):
    """The dump is encoded in one piece and written once; the artifact is
    byte for byte what `json.dump(snapshot, fh, default=repr)` gave, data
    that JSON does not know included, and no `.tmp` is left beside it."""
    import io
    clock = {"t": 1.5}
    tr = Tracer("N1", lambda: clock["t"], ring_size=8,
                dump_dir=str(tmp_path), min_dump_interval=5.0)
    tr.emit("stage", "k0", {"n": 3, "dur": 0.25, "who": ("a", "b")})
    tr.emit("stage", "k1", {"odd": {1, 2} - {2}, "raw": b"\x00\xff"})
    tr.anomaly("view_change_start", None)       # written before it returns
    dumps = sorted(tmp_path.iterdir())
    assert [d.name for d in dumps] == ["N1-flight-0.json"]
    plain = io.StringIO()
    json.dump(tr.snapshot(), plain, default=repr)
    assert dumps[0].read_text() == plain.getvalue()
    assert json.loads(dumps[0].read_text())["events"][-1][1] \
        == "anomaly.view_change_start"
