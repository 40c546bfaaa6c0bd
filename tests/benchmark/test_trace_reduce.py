"""(d) The trace reduction: busy union, idle share and per-program time,
on a hand-made stream and on the fixture cut from a real TPU trace."""
import json
import os

import pytest

from bench_paths import ROOT
from benchmarks import trace_reduce

DEV = "/device:TPU:0"
MS = 1_000_000


def hand_made():
    # host thread spans 0..100 ms; two executions of the verify program
    # (10..30 ms and 50..60 ms) and one of another (70..75 ms); the op line
    # leaves a 2 ms hole inside the first execution
    return [
        ("/host:CPU", "python3", "drive", 0, 100 * MS),
        (DEV, "XLA Modules", "jit_verify_kernel_bytes(1)", 10 * MS, 20 * MS),
        (DEV, "XLA Modules", "jit_verify_kernel_bytes(1)", 50 * MS, 10 * MS),
        (DEV, "XLA Modules", "jit_sha256(2)", 70 * MS, 5 * MS),
        (DEV, "XLA Ops", "fusion.1", 10 * MS, 8 * MS),
        (DEV, "XLA Ops", "fusion.2", 20 * MS, 10 * MS),
        (DEV, "XLA Ops", "fusion.1", 50 * MS, 10 * MS),
        (DEV, "XLA Ops", "copy.3", 70 * MS, 5 * MS),
        (DEV, "XLA Ops", "copy.3", 72 * MS, 1 * MS),        # overlaps
        (DEV, "Steps", "0", 0, 100 * MS),                   # not an op
    ]


def test_busy_union_idle_and_program_time():
    out = trace_reduce.reduce(hand_made())
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.033)        # 8 + 10 + 10 + 5
    prog = out["programs"]["jit_verify_kernel_bytes(1)"]
    assert prog["count"] == 2 and prog["time_s"] == pytest.approx(0.030)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.018)]
    assert out["idle_gaps"][:2] == [
        ["after the last program", pytest.approx(0.025)],
        ["before jit_verify_kernel_bytes(1)", pytest.approx(0.020)]]


def test_out_of_order_events_and_two_chips():
    events = list(reversed(hand_made())) + [
        ("/device:TPU:1", "XLA Modules", "jit_verify_kernel_bytes(1)",
         0, 50 * MS)]
    out = trace_reduce.reduce(events)
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx((0.033 + 0.050) / 2)
    assert out["programs"]["jit_verify_kernel_bytes(1)"]["count"] == 3


def test_no_device_plane_reads_nothing():
    out = trace_reduce.reduce([("/host:CPU", "python3", "x", 0, 5)])
    assert out["chips"] == 0 and out["busy_s"] == 0.0
    assert out["window_s"] == 0.0 and out["programs"] == {}


def test_a_plane_without_an_op_line_is_busy_by_its_programs():
    events = [e for e in hand_made() if e[1] != "XLA Ops"]
    out = trace_reduce.reduce(events)
    assert out["busy_s"] == pytest.approx(0.035)        # 20 + 10 + 5
    # the breakdown then lists programs where it has no operations
    assert out["device_ops"][0] == ["jit_verify_kernel_bytes(1)",
                                    pytest.approx(0.030)]


def test_the_breakdown_keeps_the_ten_longest():
    events = [("/host:CPU", "python3", "drive", 0, 1000 * MS)]
    for i in range(15):
        events.append((DEV, "XLA Modules", f"jit_p{i}(0)", i * 50 * MS,
                       (i + 1) * MS))
        events.append((DEV, "XLA Ops", f"op.{i}", i * 50 * MS, (i + 1) * MS))
    out = trace_reduce.reduce(events)
    assert len(out["device_ops"]) == len(out["idle_gaps"]) == 10
    assert out["device_ops"][0] == ["op.14", pytest.approx(0.015)]
    assert out["idle_gaps"][0] == ["after the last program",
                                   pytest.approx(0.285)]


def test_fixture_from_a_real_tpu_trace():
    with open(os.path.join(ROOT, "benchmarks", "fixtures",
                           "trace_events.json")) as fh:
        fixture = json.load(fh)
    out = trace_reduce.reduce([tuple(e) for e in fixture["events"]])
    want = fixture["expected"]
    assert out["chips"] == want["chips"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["window_s"] == pytest.approx(want["window_s"])
    verify = [p for n, p in out["programs"].items() if "verify_kernel" in n]
    assert sum(p["count"] for p in verify) == want["verify_executions"]
    assert sum(p["time_s"] for p in verify) == \
        pytest.approx(want["verify_time_s"])
    assert 0.0 < out["busy_s"] <= out["window_s"]


def test_the_benchmarks_own_span_is_the_window():
    events = hand_made() + [
        ("/host:CPU", "python3", trace_reduce.WINDOW_EVENT, 20 * MS, 45 * MS)]
    out = trace_reduce.reduce(events)
    assert out["window_s"] == pytest.approx(0.045)      # 20..65 ms
    # busy inside it: 20..30 and 50..60; the first execution (10..30 ms) is
    # cut by the window's edge and is not counted as a whole run
    assert out["busy_s"] == pytest.approx(0.020)
    prog = out["programs"]["jit_verify_kernel_bytes(1)"]
    assert prog["count"] == 1 and prog["time_s"] == pytest.approx(0.010)


def test_hold_trace_spans_its_window(tmp_path):
    cost = trace_reduce.hold_trace(str(tmp_path), 0.05)
    assert cost["held_s"] >= 0.05 and set(cost) == {"start_s", "held_s",
                                                    "stop_s"}
    spans = [dur for _plane, _line, name, _start, dur
             in trace_reduce.xplane_events(
                 trace_reduce.find_xplane(str(tmp_path)))
             if name == trace_reduce.WINDOW_EVENT]
    assert len(spans) == 1 and spans[0] >= 0.05e9
    # no device plane on the CPU: nothing is read under a device's name
    assert trace_reduce.reduce_dir(str(tmp_path))["chips"] == 0
