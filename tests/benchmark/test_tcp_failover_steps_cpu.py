"""What a survivor does between NEW_VIEW accepted and its first fresh order,
as the `tcp_failover` cell prints it at test size on the CPU (PR 49).

One rehearsal of `tcp_failover.primary_kill` through `probes/cell_layers.py`
(a process of its own: the probe patches `benchmarks.cell`), read four
ways: the benchmark's own `failover` lines carry every survivor's episode
step by step; the probe's `probe_failover_steps` turns each into the spans
`recertify + first_cut + first_round = fresh_order` and their medians (what
six per-layer metrics would read once `topologies/tcp_failover.py` hands
them to a reader: a `benchmark` PR's edit); `probe_bls` reads
`bls.ppr_multi_sig` of every validator, not the first alone, and
`probe_propagation` what each queued after executing it. A labelled
rehearsal: no number here is a device number, and no assertion rests on
which kind of batch a survivor happened to order first."""
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from plenum_tpu.consensus.ordering_service import VC_STEPS

CELL = "tcp_failover.primary_kill"
LIMIT_S = 300           # the run takes ~25 s alone
SPANS = ("recertify_ms", "first_cut_ms", "first_round_ms")


@pytest.fixture(scope="module")
def lines():
    """run.py's parent ends the cell's process group on every way out, the
    deadline of this call included."""
    proc = subprocess.run(
        [sys.executable, os.path.join(bench_paths.ROOT, "probes",
                                      "cell_layers.py"),
         "--workload", CELL, "--seed", "2147483693", "--seconds", "6",
         "--trace", "0", "--rehearse-cpu"],
        cwd=bench_paths.ROOT, capture_output=True, text=True,
        timeout=LIMIT_S)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = [json.loads(x) for x in proc.stdout.splitlines()
           if x.startswith("{")]
    assert got[-1]["correct"] is True and got[-1]["failed"] == 0, got[-1]
    return got


def episodes(lines) -> dict:
    return {x["failover"]["node"]: x["failover"]["view_change"]
            for x in lines if isinstance(x.get("failover"), dict)}


def check_the_failover_lines(lines):
    seen = episodes(lines)
    assert sorted(seen) == ["Node2", "Node3", "Node4"]
    for name, vc in seen.items():
        e = vc["ordering"]
        assert all(e[k] is not None for k in VC_STEPS), (name, e)
        assert e["recertified_ms"] <= e["first_cut_ms"] \
            <= e["first_cut_apply_ms"] <= e["fresh_ordered_ms"], (name, e)
        assert e["first_prepared_ms"] <= e["first_ordered_ms"] \
            <= e["fresh_ordered_ms"], (name, e)
        assert e["first_ordered_kind"] in ("recertified", "fresh")
        if e["first_ordered_kind"] == "fresh":
            assert e["fresh_ordered_ms"] - e["first_ordered_ms"] == 0
            assert e["cited_ordered"] == 0
        assert e["cited_reapplied"] == e["cited_ordered"]
        assert e["cited_reapplied"] + e["cited_recertified_only"] \
            == e["reordered_batches"], (name, e)
        assert e["cycles"] >= 1
        assert 0 < e["longest_cycle_ms"] <= e["fresh_ordered_ms"]
        bls = e["bls"]
        assert bls["submitted"] == bls["offloaded"] + bls["inline"] >= 1
        assert bls["start_join_ms"] >= 0 and bls["depth_at_new_view"] >= 0
        # the stamp pair of before is there as it was, beside the steps
        assert set(vc["last"]["phases_s"]) >= {"new_view_to_order"}
        assert vc["last"]["phases_s"]["new_view_to_order"] > 0


def check_the_probes_steps(lines):
    probe = next(x for x in lines if "probe_failover_steps" in x)
    steps = probe["probe_failover_steps"]
    seen = episodes(lines)
    assert sorted(steps["survivors"]) == sorted(seen)
    for name, row in steps["survivors"].items():
        assert row["episode"] == seen[name]["ordering"]
        # to the millisecond: the three spans are the whole
        assert sum(row[k] for k in SPANS) == pytest.approx(
            row["fresh_order_ms"], abs=1e-3), (name, row)
        assert all(row[k] >= 0 for k in SPANS), (name, row)
    medians = steps["medians"]
    assert sorted(medians) == sorted(
        "failover." + k for k in SPANS + (
            "fresh_order_ms", "bls_join_wait_ms", "first_order_fresh_share"))
    assert all(v is not None for v in medians.values()), medians
    assert 0.0 <= medians["failover.first_order_fresh_share"] <= 100.0


def check_every_validators_bls(lines):
    probe = next(x for x in lines if "probe_bls" in x)["probe_bls"]
    # all four by name; the victim is gone at the window's end
    assert sorted(probe) == ["Node1", "Node2", "Node3", "Node4"]
    assert probe["Node1"] is None
    for name in ("Node2", "Node3", "Node4"):
        got = probe[name]["ppr_multi_sig"]
        assert got["known"] + got["paired"] > 0, (name, got)
        assert (got["paired_mean_ms"] is None) == (got["paired"] == 0)
        assert got["joined_ms"] >= 0
        assert probe[name]["offloaded"] + probe[name]["inline"] > 0


def check_every_validators_propagation(lines):
    probe = next(x for x in lines if "probe_propagation" in x)
    assert sorted(probe["probe_propagation"]) == [
        "Node1", "Node2", "Node3", "Node4"]
    for name, got in probe["probe_propagation"].items():
        assert (got is None) == (name == "Node1"), (name, got)
        if got is not None:
            assert got["forwarded_after_executed"] >= 0
    # what a survivor queued after executing it is beside its episode
    for row in probe["probe_failover_steps"]["survivors"].values():
        assert row["forwarded_after_executed"] >= 0


@pytest.mark.parametrize("check", [check_the_failover_lines,
                                   check_the_probes_steps,
                                   check_every_validators_bls,
                                   check_every_validators_propagation],
                         ids=lambda f: f.__name__)
def test_the_failover_rehearsal_prints(lines, check):
    check(lines)
