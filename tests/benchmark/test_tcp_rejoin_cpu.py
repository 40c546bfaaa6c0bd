"""The `tcp_rejoin` launcher at test size on the CPU: a sound run (a
validator that is no instance's primary SIGKILLed a tenth of the way into
the window, started again 0.1 s later, caught up under load, voting again
before the window closes, its disk at the kill a prefix of the final
ledger) comes out correct and leaves nothing running; the control in which
the victim is not started again, and the controls on its disk, come out as
they must. Labelled rehearsals: no metric is written. No assertion rests on
which batch a disk happened to hold at the kill, on how many rounds the
catch-up took, or on how long the process needed to boot."""
import json
import os
import signal
import struct
import uuid
import zlib

import msgpack
import pytest

import bench_paths  # noqa: F401
from benchmarks import (cell, manifest, reaper, readers, reference,
                        reference_rejoin, reference_store)
from benchmarks.topologies import tcp_rejoin

CELL = "tcp_rejoin.backup_restart"
SECONDS = 10.0
LIMIT_S = 240           # a run takes ~25 s alone


@pytest.fixture
def time_limit():
    """Each run has a limit of its own: past it the test fails from
    inside, and cell.main's way out stops every child."""
    def on_alarm(signum, frame):
        raise TimeoutError(f"the run passed its {LIMIT_S} s limit")
    kept = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, kept)


def run_cell(tmp_path, capsys, monkeypatch):
    marker = f"rejointest{os.getpid()}{uuid.uuid4().hex[:8]}"
    monkeypatch.setenv(reaper.MARKER_VAR, marker)
    result = tmp_path / "result.json"
    rc = cell.main(["--workload", CELL, "--seed", "2147483693",
                    "--seconds", str(SECONDS), "--trace", "0",
                    "--rehearse-cpu", "--run-dir", str(tmp_path),
                    "--result", str(result)])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert reaper.marked(marker) == []          # nothing left running
    compared = {x["compared"]["check"]: x["compared"]
                for x in lines if "compared" in x}
    return rc, json.loads(result.read_text()), lines, compared


def victims_log(launcher) -> str:
    return os.path.join(launcher.run_dir, launcher.victim, "data",
                        "domain_log", "kv.kvn")


def cut_last_row(launcher) -> None:
    """The victim's domain txn log loses the last record it flushed."""
    path = victims_log(launcher)
    with open(path, "rb") as fh:
        _, ends = reference_store.scan_native(fh.read(), with_ends=True)
    os.truncate(path, ends[-2])


def alter_a_row(launcher) -> None:
    """One transaction inside the victim's log, past the preload, is
    rewritten in place with a sound checksum and the same length: the
    program's roots (its hash store is not recomputed at a restart) keep
    agreeing with the pool's."""
    path = victims_log(launcher)
    with open(path, "rb") as fh:
        data = bytearray(fh.read())
    rows, ends = reference_store.scan_native(bytes(data), with_ends=True)
    head = struct.Struct("<IBII")
    n = len(rows) - 20                      # well inside what it flushed
    op, key, value = rows[n]
    txn = msgpack.unpackb(value, raw=False, strict_map_key=False)
    assert int.from_bytes(key, "big") > launcher.sizes["preload_dids"]
    req_id = txn["txn"]["metadata"]["reqId"]
    txn["txn"]["metadata"]["reqId"] = req_id + 1 if req_id % 10 != 9 \
        else req_id - 1                     # the same number of digits
    forged = msgpack.packb(txn, use_bin_type=True)
    assert len(forged) == len(value) and forged != value
    start = ends[n - 1]
    body = bytes([op]) + struct.pack("<II", len(key), len(forged)) \
        + key + forged
    data[start:ends[n]] = struct.pack("<I", zlib.crc32(body)) + body
    assert head.size + len(key) + len(forged) == ends[n] - start
    with open(path, "wb") as fh:
        fh.write(data)


@pytest.mark.usefixtures("time_limit")
def test_backup_restarted_in_the_window_and_a_sound_run_is_correct(
        tmp_path, capsys, monkeypatch):
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is True and "rehearsal" in got, compared
    assert got["attempted"] > 0 and got["failed"] == 0 and got["metrics"] == {}
    assert all(r["ok"] for r in compared.values()), compared

    fault = next(x["fault"] for x in lines if "fault" in x)
    assert fault["victim"] == "Node4" and not fault["victim_was_primary"]
    assert 0.095 * SECONDS <= fault["kill_offset_s"] <= 0.15 * SECONDS
    # the client had the victim back before the window closed
    assert fault["connections_left"] == ["Node1", "Node2", "Node3", "Node4"]
    for check, limit in (
            ("fault.kill_share_of_window", [0.095, 0.15]),
            ("fault.victim_is_primary_of_instances", 0),
            ("rejoin.down_s", 120),
            ("rejoin.phases_missing_or_out_of_order", 0),
            ("rejoin.victim_replies_to_window_writes", 1),
            ("rejoin.survivors_whose_last_multi_sig_counts_victim", 1),
            ("rejoin.view_changes_started", 0),
            ("rejoin.validators_in_another_view", 0),
            ("rejoin.txns_caught_up_minus_txns_served", 0),
            ("rejoin.victim_disk_txns_differing_from_final", 0),
            ("rejoin.victim_disk_prefix_root_mismatches", 0),
            ("durable.post_restart_writes_acknowledged", 16),
            ("nodes.distinct_views", 1),
            ("reference.root_mismatches", 0),
            ("ledger.acknowledged_writes_lost", 0)):
        assert compared[check]["limit"] == limit and compared[check]["ok"]

    rejoin = next(x["rejoin"] for x in lines if "rejoin" in x)
    assert rejoin["victim"] == "Node4" and rejoin["engine"] == "native"
    # the restart was issued about 0.1 s after the kill, and the delay
    # really used is printed
    assert 0.1 <= rejoin["restart_delay_s"] < 1.0
    phases = rejoin["phases_s"]
    assert [phases[p] for p in tcp_rejoin.PHASES] \
        == sorted(phases[p] for p in tcp_rejoin.PHASES)
    assert phases["process_start"] == 0.0 < phases["stores_replayed"]
    # under load, inside the window, by its own quorum
    first = compared["rejoin.first_3pc_order_at_s"]
    assert first["got"] <= first["limit"] <= SECONDS + cell.DRAIN_S + 0.1
    assert rejoin["start_line_at_s"] < rejoin["window_closed_at_s"]
    assert rejoin["catchup_txns"] > 0 and len(rejoin["rounds"]) >= 1
    assert rejoin["catchup_txns"] == sum(r["txns"] for r in rejoin["rounds"])
    assert rejoin["stash"]["held"] == rejoin["stash"]["replayed"]
    assert rejoin["recovery"]["rejoined"]["rounds"] == len(rejoin["rounds"])
    assert rejoin["recovery"]["seconds"]["phases"]["catchup_started"] \
        == phases["catchup_started"]
    assert rejoin["last_ordered_3pc"][1] \
        > rejoin["rounds"][-1]["target_3pc"][1]
    seeders = next(x["seeders"] for x in lines if "seeders" in x)
    assert seeders["Node4"]["txns_served"] == 0
    assert all(seeders[n]["reqs"] > 0 and seeders[n]["serve"]["sum_s"] > 0
               for n in ("Node1", "Node2", "Node3"))
    on_disk = next(x["on_disk"] for x in lines if "on_disk" in x)
    assert on_disk["preload"] == 4097 < on_disk["disk_txns"] \
        < on_disk["final_txns"]
    assert on_disk["disk_root"] == on_disk["final_prefix_root"] \
        != on_disk["final_root"]
    # the seeder's counters were read from the first validator around
    # the window
    counters = next(x["counters"] for x in lines if "counters" in x)
    assert counters["after"]["seeder.serve_seconds"] \
        > counters["before"]["seeder.serve_seconds"] == 0.0
    assert counters["after"]["seeder.clock_seconds"] \
        - counters["before"]["seeder.clock_seconds"] >= SECONDS
    numbers = next(x["numbers"] for x in lines if "rehearsal" in x)
    assert {"commit_latency_p50_ms", "commit_latency_p95_ms",
            "committed_writes_per_s", "setup_s"} <= set(numbers)


@pytest.mark.usefixtures("time_limit")
def test_control_victim_never_started_again_is_not_correct(
        tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(tcp_rejoin.Launcher, "restart_victim",
                        lambda self: None)
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is False and got["failed"] == 0
    failed = {c for c, r in compared.items() if not r["ok"]}
    # the victim was down to the end of the drain: that alone decides
    assert failed == {"rejoin.down_s", "nodes.fallback_problems"}, compared
    assert compared["rejoin.down_s"]["got"] is None
    assert "not started again" in compared["rejoin.down_s"]["note"]
    fault = next(x["fault"] for x in lines if "fault" in x)
    assert fault["connections_left"] == ["Node1", "Node2", "Node3"]
    # three survivors are a quorum: every write was served all the same,
    # and the victim, started by the launcher for the comparisons,
    # converges afterwards
    assert compared["nodes.distinct_views"]["ok"]
    assert compared["fault.kill_share_of_window"]["ok"]


@pytest.mark.usefixtures("time_limit")
@pytest.mark.parametrize("damage, correct, failed_by", [
    (cut_last_row, True, set()),
    (alter_a_row, False, {"rejoin.victim_disk_txns_differing_from_final",
                          "rejoin.victim_disk_prefix_root_mismatches",
                          "nodes.fallback_problems"})],
    ids=["cut_back_by_a_batch_converges", "altered_inside_its_prefix"])
def test_control_the_victims_disk_after_the_kill(
        tmp_path, capsys, monkeypatch, damage, correct, failed_by):
    monkeypatch.setattr(tcp_rejoin.Launcher, "after_the_kill", damage)
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is correct and got["failed"] == 0
    assert {c for c, r in compared.items() if not r["ok"]} == failed_by, \
        compared
    # either way the program's roots agree: the four converge, and the
    # victim votes again inside the window
    assert compared["nodes.distinct_views"]["ok"]
    assert compared["reference.root_mismatches"]["ok"]
    assert compared["rejoin.first_3pc_order_at_s"]["ok"]
    if not correct:
        differing = compared["rejoin.victim_disk_txns_differing_from_final"]
        assert differing["got"] == 1


# --- the pieces ---------------------------------------------------------------


def _txn(i: int) -> dict:
    return {"txn": {"type": "1", "data": {"dest": f"did{i}", "verkey": "v"},
                    "metadata": {"from": "t", "reqId": i}},
            "txnMetadata": {"seqNo": i}}


def test_prefix_check_is_plain_and_exact():
    final = [_txn(i) for i in range(1, 41)]
    disk = {i: _txn(i) for i in range(1, 26)}
    got = reference_rejoin.prefix_check(disk, final)
    assert got["disk_txns"] == 25 and got["final_txns"] == 40
    assert got["differing"] == [] and got["disk_txns_past_a_hole"] == 0
    assert got["disk_root"] == got["final_prefix_root"] \
        == reference.merkle_root(
            reference.leaf_bytes(t) for t in final[:25]).hex()
    assert got["final_root"] == reference.replay(final)[0].hex()
    # an altered transaction, a hole, and a disk that runs past the end
    disk[7] = dict(_txn(7), txnMetadata={"seqNo": 7, "x": 1})
    got = reference_rejoin.prefix_check(disk, final)
    assert got["differing"] == [7]
    assert got["disk_root"] != got["final_prefix_root"]
    del disk[7]
    got = reference_rejoin.prefix_check(disk, final)
    assert (got["disk_txns"], got["disk_txns_past_a_hole"]) == (6, 18)
    got = reference_rejoin.prefix_check(
        {i: _txn(i) for i in range(1, 51)}, final)
    assert got["disk_longer_than_final"] == 10


def test_the_parent_of_this_deployment_is_refused_at_once(monkeypatch):
    """A program without the rejoin clock (the parent) is refused before
    anything is started: the driver's try of the cell on the parent ends
    in a second, with a message."""
    from plenum_tpu.common.metrics import MetricsName
    monkeypatch.delattr(MetricsName, "SEEDER_SERVE_TIME")
    with pytest.raises(SystemExit, match="no `rejoin` block"):
        tcp_rejoin.Launcher(manifest.cell(CELL)["config"], "/nonexistent",
                            1, True)


@pytest.mark.parametrize("key", [
    "nodes", "f", "protocol_instances", "kv", "kv_engine", "settings",
    "service", "service_rehearsal", "shapes", "sizes", "rehearsal_sizes",
    "trace_seconds"])
def test_the_deployment_is_the_durable_pools_but_for_the_fault(key):
    mine = manifest.cell(CELL)["config"]
    durable = manifest.cell("tcp_durable.write_steady")["config"]
    assert mine[key] == durable[key]


def test_the_fault_the_guarantees_and_the_drive_are_stated():
    mine = manifest.cell(CELL)["config"]
    durable = manifest.cell("tcp_durable.write_steady")["config"]
    failover = manifest.cell("tcp_failover.primary_kill")["config"]
    assert "crash" not in mine and mine["topology"] == "tcp_rejoin"
    assert len(mine["source"]) <= 200
    assert "plenum/test/node_catchup" in mine["source"] \
        and "plenum/test/restart" in mine["source"]
    fault = mine["fault"]
    assert fault["signal"] == "SIGKILL"
    assert (fault["at_share_of_window"], fault["restart_after_s"]) \
        == (0.1, 0.1)
    for shared in ("restart_deadline_s", "liveness_writes",
                   "liveness_deadline_s", "traffic"):
        assert fault[shared] == failover["fault"][shared]
    for kept in ("write_acknowledged_on", "nodes_converge",
                 "no_acknowledged_write_lost", "read_accepted_on",
                 "client_signatures", "reply_after_flush"):
        assert mine["guarantees"][kept] == durable["guarantees"][kept]
    assert {"served_through_restart", "no_election_for_a_backup",
            "victim_rejoins_under_load", "no_fork_across_restart"} \
        <= set(mine["guarantees"])
    assert set(durable["assumed"]) < set(mine["assumed"])
    assert any("RestartSec" in a for a in mine["assumed"])
    assert list(mine["reduced"]) == ["preload_dids"]
    entry = next(c for c in manifest.benchmark()["configs"]
                 if c["name"] == "pool4_tcp_rejoin")
    assert entry["reduced"] == ["preload_dids"]
    assert "reads_from" in mine and "fault_is" in mine
    drive, control = manifest.cell(CELL)["workload"], manifest.cell(
        "tcp_durable.write_steady")["workload"]
    assert (drive["drive"], drive["rate_per_s"]) == (
        control["drive"], control["rate_per_s"]) == ("open_loop", 320)
    assert drive["rehearsal"] == {"rate_per_s": 60}
    cell_entry = manifest.cell(CELL)["entry"]
    assert (cell_entry["traffic"], cell_entry["chips"]) == ("mixed_writes", 1)


OWN = {"rejoin.down_ms": 9000.0, "rejoin.boot_ms": 7000.0,
       "rejoin.catchup_ms": 2500.0, "rejoin.to_first_order_ms": 150.0,
       "rejoin.catchup_txns_per_s": 1200.0, "rejoin.catchup_rounds": 2.0,
       "seeder.serve_p50_ms": 2.0, "seeder.busy_share": 0.5,
       "rejoin.view_changes": 0.0}


@pytest.mark.parametrize("name", sorted(OWN))
def test_each_new_metrics_reader_finds_its_series(name):
    """The data files load and read what the launcher hands over (the
    names in `samples()`, `snapshot()` and the totals)."""
    samples = {"rejoin.down_s": [9.0], "rejoin.boot_s": [7.0],
               "rejoin.catchup_s": [2.5], "rejoin.to_first_order_s": [0.15],
               "seeder.serve_s": [0.001, 0.002, 0.004]}
    obs = {"samples": samples, "counters": {
        "before": {"seeder.serve_seconds": 0.0,
                   "seeder.clock_seconds": 100.0},
        "after": {"seeder.serve_seconds": 0.115,
                  "seeder.clock_seconds": 123.0,
                  "rejoin.catchup_txns": 3000, "rejoin.catchup_seconds": 2.5,
                  "rejoin.catchup_rounds": 2, "rejoin.rejoins": 1,
                  "rejoin.view_changes_started": 0,
                  "rejoin.validators": 4}}}
    got = readers.read(manifest.metric_spec("per_layer", name), obs)
    assert got == pytest.approx(OWN[name])
    entry = next(m for m in manifest.benchmark()["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["layer"] == "catch-up"
    assert entry["moves"] == "commit_latency_p95_ms"


def test_the_cell_reports_the_durable_cells_metrics_and_nine_of_its_own():
    mine = {m["name"] for m in manifest.benchmark()["per_layer"]
            if CELL in m.get("workloads", [])}
    durable = {m["name"] for m in manifest.benchmark()["per_layer"]
               if "tcp_durable.write_steady" in m.get("workloads", [])}
    assert mine == durable | set(OWN)
    e2e = {m["name"] for m in manifest.metrics_of(CELL, "end_to_end")}
    assert e2e == {"committed_writes_per_s", "commit_latency_p50_ms",
                   "commit_latency_p95_ms", "setup_s"}
