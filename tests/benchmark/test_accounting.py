"""(c) Metric arithmetic on hand-made (due, acknowledged) pairs, and the
f+1 matching-reply rule."""
import pytest

import bench_paths  # noqa: F401
from benchmarks.accounting import Tracker, quantile, window_numbers


def test_quantile_is_nearest_rank():
    values = [5, 1, 4, 2, 3, 6, 7, 8, 9, 10]
    assert quantile(values, 0.5) == 5
    assert quantile(values, 0.95) == 10
    assert quantile(values, 0.90) == 9
    assert quantile([42], 0.95) == 42
    with pytest.raises(ValueError):
        quantile([], 0.5)


def test_window_numbers_by_hand():
    # window [100, 110), drained at 113; ten requests due one a second
    due = {i: 100.0 + i for i in range(10)}
    due["after"] = 110.5                        # due outside: not attempted
    acked = {i: 100.0 + i + 0.1 * (i + 1) for i in range(8)}
    acked[8] = 112.0                            # in the drain: ok, 4.0 s
    acked[9] = 114.0                            # late: failed
    acked["after"] = 111.0
    out = window_numbers(due, acked, 100.0, 110.0, 113.0)
    assert out["attempted"] == 10 and out["failed"] == 1
    assert out["acked_in_window"] == 8 and out["per_s"] == 0.8
    # latencies: 0.1 .. 0.8, 4.0, and the failed one at 113 - 109 = 4.0
    assert out["latency_samples"] == 10
    assert out["latency_p50_ms"] == pytest.approx(500.0)
    assert out["latency_p95_ms"] == pytest.approx(4000.0)


def test_unacknowledged_counts_failed_and_enters_the_tail():
    due = {i: float(i) for i in range(4)}
    out = window_numbers(due, {0: 0.5}, 0.0, 4.0, 5.0)
    assert out["attempted"] == 4 and out["failed"] == 3
    assert out["latency_p95_ms"] == pytest.approx(4000.0)


@pytest.mark.parametrize("quorum,expect", [(2, False), (1, True)])
def test_one_reply_acknowledges_only_at_quorum_one(quorum, expect):
    t = Tracker(quorum)
    t.expect("k", 1.0, 1.0)
    assert t.on_reply("k", "Node1", {"v": 1}, 2.0) is expect
    assert ("k" in t.acked) is expect


def test_matching_means_equal_results():
    t = Tracker(2)
    t.expect("k", 1.0, 1.0)
    assert not t.on_reply("k", "Node1", {"v": 1}, 2.0)
    assert not t.on_reply("k", "Node2", {"v": 2}, 2.1)     # differs
    assert not t.on_reply("k", "Node1", {"v": 1}, 2.2)     # same node again
    assert t.open == 1
    assert t.on_reply("k", "Node3", {"v": 1}, 2.3)
    assert t.acked["k"] == 2.3 and t.open == 0 and t.quorum_of("k") == 2
    assert not t.on_reply("unknown", "Node1", {"v": 1}, 2.4)


def test_nack_closes_the_slot_once():
    t = Tracker(2)
    t.expect("k", 1.0, 1.0)
    t.on_nack("k", "Node1", "REQNACK: bad")
    t.on_nack("k", "Node2", "REQNACK: bad")
    assert t.open == 0 and t.nacked["k"].startswith("REQNACK")


def test_due_but_never_sent_is_attempted_and_failed():
    t = Tracker(2)
    t.expect("sent", 1.0, 1.001)
    t.expect("unsent", 2.0, None)
    assert t.open == 1 and "unsent" not in t.sent
    out = window_numbers(t.due, t.acked, 0.0, 3.0, 4.0)
    assert out["attempted"] == 2 and out["failed"] == 2


class Req:
    def __init__(self, i):
        self.identifier, self.req_id = "did", i


def test_open_loop_releases_by_due_time_whatever_was_acknowledged():
    from benchmarks.accounting import Feeder
    reqs = [Req(i) for i in range(5)]
    t = Tracker(2)
    f = Feeder(reqs, {"due": [0.0, 0.1, 0.2, 0.3, 0.95]}, 1.0, t, 100.0)
    assert [r.req_id for r in f.take(100.15)] == [0, 1]     # none acked
    assert t.due[("did", 1)] == pytest.approx(100.1)
    assert t.sent[("did", 1)] == 100.15 and f.next_due() == 100.2
    assert not f.over(100.15)
    # a pass that wakes past the close still sends what was due before it
    assert [r.req_id for r in f.take(101.2)] == [2, 3, 4]
    assert f.over(101.2) and f.close(101.2, 3.0) == 104.0
    assert f.times(104.0, 102.0)["t_drained"] == 102.0      # drained early


def test_open_loop_counts_what_it_never_sent():
    from benchmarks.accounting import Feeder
    t = Tracker(2)
    f = Feeder([Req(0), Req(1)], {"due": [0.0, 0.5]}, 1.0, t, 0.0)
    f.take(0.1)
    f.close(1.0, 3.0)
    assert ("did", 1) in t.due and ("did", 1) not in t.sent and t.open == 1


def test_closed_loop_keeps_the_cap_and_stops_at_the_close():
    from benchmarks.accounting import Feeder
    reqs = [Req(i) for i in range(10)]
    t = Tracker(1)
    f = Feeder(reqs, {"in_flight": 3}, 1.0, t, 0.0)
    assert len(f.take(0.1)) == 3 and f.take(0.2) == []
    t.on_reply(("did", 0), "Node1", {}, 0.3)
    assert [r.req_id for r in f.take(0.3)] == [3]
    assert f.take(1.0) == [] and f.over(1.0)
    f.close(1.0, 3.0)
    assert len(t.due) == 4                      # the rest was never due


def test_warm_up_runs_until_everything_is_answered():
    from benchmarks.accounting import Feeder
    t = Tracker(1)
    f = Feeder([Req(0)], {"in_flight": 8}, None, t, 0.0)
    assert len(f.take(0.1)) == 1 and not f.over(0.1)
    t.on_reply(("did", 0), "Node1", {}, 0.2)
    assert f.over(0.2) and f.close(0.25, 60.0) == 60.25
