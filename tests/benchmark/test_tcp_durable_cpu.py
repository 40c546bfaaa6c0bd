"""The `tcp_durable` launcher and its reader of the disks, at test size on
the CPU: a sound run (kill, on-disk comparison, restart, reconnect,
comparisons against the restarted pool, liveness) comes out correct and
leaves nothing running; the three durability controls come out as they
must; `reference_store.py` reads what the engines wrote. Labelled
rehearsals: no metric is written."""
import json
import os
import subprocess
import sys

import pytest

import bench_paths  # noqa: F401
from benchmarks import cell, manifest, reaper, reference_store
from benchmarks.topologies import tcp_durable

CELL = "tcp_durable.write_steady"
HERE = os.path.dirname(os.path.abspath(__file__))


def run_cell(tmp_path, capsys, monkeypatch, *extra):
    marker = "durabletest" + tmp_path.name
    monkeypatch.setenv(reaper.MARKER_VAR, marker)
    result = tmp_path / "result.json"
    rc = cell.main(["--workload", CELL, "--seed", "2147483659",
                    "--seconds", "2.0", "--trace", "0", "--rehearse-cpu",
                    "--run-dir", str(tmp_path), "--result", str(result),
                    *extra])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert reaper.marked(marker) == []          # nothing left running
    compared = {x["compared"]["check"]: x["compared"]
                for x in lines if "compared" in x}
    return rc, json.loads(result.read_text()), lines, compared


def cut_last_row(run_dir, name: str) -> None:
    """The validator's domain txn log loses the last record it flushed."""
    path = os.path.join(str(run_dir), name, "data", "domain_log", "kv.kvn")
    with open(path, "rb") as fh:
        _, ends = reference_store.scan_native(fh.read(), with_ends=True)
    os.truncate(path, ends[-2])


# --- the launcher ---------------------------------------------------------------


def test_kill_restart_reconnect_and_a_sound_run_is_correct(
        tmp_path, capsys, monkeypatch):
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch,
                                        "--check", "2")
    assert rc == 0 and got["correct"] is True and "rehearsal" in got
    assert got["attempted"] > 0 and got["failed"] == 0 and got["metrics"] == {}
    assert all(r["ok"] for r in compared.values()), compared

    setup = next(x["durable_setup"] for x in lines if "durable_setup" in x)
    assert set(setup["engines"].values()) == {"native"}
    assert setup["run_dir_filesystem"] != "unknown"
    crash = next(x["crash"] for x in lines if "crash" in x)
    assert crash["killed"] == 4 and crash["tail_acknowledged_at_kill"] >= 48
    assert crash["tail_sent"] > crash["tail_acknowledged_at_kill"]
    on_disk = next(x["on_disk"] for x in lines if "on_disk" in x)
    assert on_disk["on_fewest_disks"] >= 2
    assert on_disk["acknowledged_writes"] > crash["tail_acknowledged_at_kill"]

    for check, limit in (("durable.acknowledged_on_fewer_than_2_disks", 0),
                         ("durable.tail_acknowledged_missing_after_restart",
                          0), ("durable.restart_s", 120),
                         ("durable.post_restart_writes_acknowledged", 16)):
        assert compared[check]["limit"] == limit and compared[check]["ok"]
    restarted = [x for x in lines if "restarted" in x]
    assert [x["restarted"] for x in restarted] == [
        "Node1", "Node2", "Node3", "Node4"]
    for x in restarted:
        rec = x["recovery"]
        assert x["engine"] == "native" and rec["restarted"] is True
        assert rec["stores"]["domain_log"]["rows"] > 4096
        assert rec["ledger_sizes"]["1"] > 4096 and "rejoined" in rec
        assert {"open_stores", "reconcile", "replay_state", "rejoin"} \
            <= set(rec["seconds"])
    # the windows' storage counters and samples were on record before
    # the crash, and the crash once, behind both windows and the controls
    counters = next(x["counters"] for x in lines if "counters" in x)
    grew = {k: counters["after"][k] - counters["before"][k]
            for k in ("storage.bytes_written", "storage.file_gets",
                      "storage.domain_txns")}
    assert all(v > 0 for v in grew.values()), grew
    assert len([x for x in lines if "crash" in x]) == 1
    numbers = next(x["numbers"] for x in lines if "rehearsal" in x)
    assert {"commit_latency_p50_ms", "committed_writes_per_s",
            "setup_s"} <= set(numbers)
    controls = [x for x in lines if "control" in x]
    assert len(controls) == 3 and not any(c["correct"] for c in controls)


def test_samples_come_from_the_record_taken_before_the_crash():
    """The restarted validators append to the metrics stores their first
    lives wrote: the window's samples are read from the folds kept at the
    crash, and the cell reports nine per-layer metrics."""
    topo = object.__new__(tcp_durable.Launcher)
    first = {"commit_path.apply_time": {"samples": [0.007]},
             "storage.flush_time": {"samples": [0.0004, 0.0006]},
             "node.ordered_batch_size": {"count": 3, "sum": 99.0}}
    both = {"commit_path.apply_time": {"samples": [0.007, 9.0]},
            "storage.flush_time": {"samples": [0.0004, 0.0006, 9.0]},
            "node.ordered_batch_size": {"count": 9, "sum": 200.0}}
    topo.first_life, topo.metrics_folds = [first], [both]
    samples, totals = topo.samples()
    assert samples["commit.apply_s"] == [0.007]
    assert samples["storage.flush_s"] == [0.0004, 0.0006]
    assert totals == {"consensus.batches": 3, "consensus.batch_reqs": 99.0}
    assert topo.metrics_folds == [both]
    mine = [m["name"] for m in manifest.benchmark()["per_layer"]
            if CELL in m.get("workloads", [])]
    assert len(mine) == 9 and {"storage.flush_p50_ms",
                               "storage.bytes_per_write",
                               "storage.gets_per_write"} <= set(mine)
    from benchmarks import readers
    obs = {"samples": samples, "counters": {
        "before": {"storage.bytes_written": 1000, "storage.domain_txns": 10},
        "after": {"storage.bytes_written": 4000, "storage.domain_txns": 11}}}
    assert readers.read(manifest.metric_spec(
        "per_layer", "storage.flush_p50_ms"), obs) == pytest.approx(0.4)
    assert readers.read(manifest.metric_spec(
        "per_layer", "storage.bytes_per_write"), obs) == 3000
    # a program without the counters (this deployment's parent): nothing
    assert readers.read(manifest.metric_spec(
        "per_layer", "storage.gets_per_write"), obs) is None


# --- the three controls ---------------------------------------------------------


def test_control_rows_held_back_one_scope_are_not_on_the_disks(
        tmp_path, capsys, monkeypatch):
    """Every validator's stores hold a scope's rows back one scope (the
    double in lagging_store_entry.py): REPLYs leave for rows that are in
    no file, and the on-disk comparison says so."""
    entry = os.path.join(HERE, "lagging_store_entry.py")
    real = subprocess.Popen

    def through_the_double(cmd, *args, **kwargs):
        if "plenum_tpu.tools.start_node" in cmd:
            at = cmd.index("-m")
            cmd = cmd[:at] + [entry] + cmd[at + 2:]
        return real(cmd, *args, **kwargs)
    monkeypatch.setattr(subprocess, "Popen", through_the_double)
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is False
    on_disk = compared["durable.acknowledged_on_fewer_than_2_disks"]
    assert on_disk["ok"] is False and on_disk["got"] > 0
    assert compared["nodes.fallback_problems"]["ok"] is False
    assert "acknowledged writes on fewer than 2 disks" in \
        compared["nodes.fallback_problems"]["note"]


def test_control_one_log_cut_after_the_kill_converges_by_catch_up(
        tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(tcp_durable.Launcher, "before_restart",
                        lambda self: cut_last_row(self.run_dir, "Node2"))
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is True, compared
    node2 = next(x for x in lines if x.get("restarted") == "Node2")
    rec = node2["recovery"]
    assert rec["reconcile"]["audit_txns_dropped"] == 1
    assert rec["rejoined"]["txns_caught_up"]["1"] >= 1
    assert compared["nodes.distinct_views"]["ok"]


def test_control_three_logs_cut_after_the_kill_is_not_correct(
        tmp_path, capsys, monkeypatch):
    def cut_three(self):
        # the validators stop at different batches: bring all four to
        # the one every disk holds, then take it off three of them
        for name in self.names[1:]:
            cut_last_row(self.run_dir, name)
    monkeypatch.setattr(tcp_durable.Launcher, "before_restart", cut_three)
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is False
    failed = {c for c, r in compared.items() if not r["ok"]}
    assert failed & {"durable.tail_acknowledged_missing_after_restart",
                     "ledger.acknowledged_writes_lost",
                     "ledger.size_minus_preload_minus_acked"}, compared
    assert compared["durable.acknowledged_on_fewer_than_2_disks"]["ok"]


# --- the configuration ----------------------------------------------------------


@pytest.mark.parametrize("key", [
    "nodes", "f", "protocol_instances", "settings", "service",
    "service_rehearsal", "shapes", "sizes", "rehearsal_sizes",
    "trace_seconds"])
def test_the_deployment_is_the_served_pools_but_for_the_store(key):
    durable = manifest.cell(CELL)["config"]
    served = manifest.cell("tcp_service.write_steady")["config"]
    assert durable[key] == served[key]


def test_guarantees_and_reduced_state_the_store():
    durable = manifest.cell(CELL)["config"]
    served = manifest.cell("tcp_service.write_steady")["config"]
    assert durable["kv"] == "file" and durable["kv_engine"] == "native"
    assert list(durable["reduced"]) == ["preload_dids"]
    kept = {k: v for k, v in served["guarantees"].items()
            if k != "durability"}
    assert {k: durable["guarantees"][k] for k in kept} == kept
    assert {"reply_after_flush", "acknowledged_is_on_disk",
            "survives_pool_crash"} <= set(durable["guarantees"])
    entry = next(c for c in manifest.benchmark()["configs"]
                 if c["name"] == "pool4_tcp_durable")
    assert entry["reduced"] == ["preload_dids"]
    drive = manifest.cell(CELL)["workload"]
    assert drive["drive"] == "open_loop" and drive["rate_per_s"] <= 720 \
        and drive["rate_per_s"] % 20 == 0


# --- the reader of the disks ----------------------------------------------------


def native_log(tmp_path, rows):
    from plenum_tpu.storage.kv_native import KvNative
    kv = KvNative(str(tmp_path / "n"))
    for key, value in rows:
        kv.put(key, value)
    return kv, str(tmp_path / "n" / "kv.kvn")


ROWS = [(i.to_bytes(8, "big"), b"value-%d" % i * (i + 1)) for i in range(9)]


def test_reader_whole_native_log_with_overwrite_and_delete(tmp_path):
    kv, _ = native_log(tmp_path, ROWS)
    kv.put(ROWS[2][0], b"again")
    kv.remove(ROWS[5][0])
    with kv.write_batch():
        kv.put(b"in-scope-1", b"a")
        kv.put(b"in-scope-2", b"b")
    got = reference_store.read_store(str(tmp_path / "n"))
    assert got == dict(kv.iterator()) and len(got) == 10
    assert got[ROWS[2][0]] == b"again" and ROWS[5][0] not in got


def test_reader_ignores_a_torn_tail(tmp_path):
    _, path = native_log(tmp_path, ROWS)
    os.truncate(path, os.path.getsize(path) - 5)
    got = reference_store.read_store(str(tmp_path / "n"))
    assert got == dict(ROWS[:-1])
    with open(path, "rb") as fh:
        assert len(fh.read()) > 0           # nothing was truncated away


def test_reader_stops_at_a_corrupt_record(tmp_path):
    _, path = native_log(tmp_path, ROWS)
    with open(path, "rb") as fh:
        data = bytearray(fh.read())
    _, ends = reference_store.scan_native(bytes(data), with_ends=True)
    data[ends[3] + 20] ^= 0x40              # inside the fifth record
    with open(path, "wb") as fh:
        fh.write(bytes(data))
    assert reference_store.read_store(str(tmp_path / "n")) == dict(ROWS[:4])


def test_reader_takes_a_batch_record_whole_or_not_at_all(tmp_path):
    from plenum_tpu.storage.kv_file import KvFile, read_log_readonly
    kv = KvFile(str(tmp_path / "f"))
    kv.put(b"alone", b"1")
    with kv.write_batch():
        for key, value in ROWS[:4]:
            kv.put(key, value)
        kv.remove(b"alone")
    with kv.write_batch():
        kv.put(b"second", b"scope")
        kv.put(b"torn", b"with it")
    kv._fh.flush()
    directory = str(tmp_path / "f")
    # once, against the program's own read-only replay of the same file
    assert reference_store.read_store(directory) == dict(
        read_log_readonly(directory))
    assert reference_store.read_store(directory) == dict(
        ROWS[:4] + [(b"second", b"scope"), (b"torn", b"with it")])
    path = os.path.join(directory, "kv.kvlog")
    os.truncate(path, os.path.getsize(path) - 3)
    assert reference_store.read_store(directory) == dict(ROWS[:4])


def test_reader_lists_a_ledgers_txns_and_counts_disks(tmp_path):
    from plenum_tpu.ledger.ledger import Ledger
    from plenum_tpu.storage.kv_native import KvNative
    disks = []
    for n, size in enumerate((3, 3, 2, 1)):
        ledger = Ledger(txn_log=KvNative(str(tmp_path / f"log{n}")))
        for i in range(size):
            ledger.append({"txn": {"metadata": {"from": "did", "reqId": i}},
                           "txnMetadata": {"seqNo": i + 1}})
        txns = reference_store.ledger_txns(str(tmp_path / f"log{n}"))
        assert txns == {i: ledger.get_by_seq_no(i)
                        for i in range(1, size + 1)}
        disks.append(reference_store.requests_of(txns))
    held = reference_store.disks_holding(
        {("did", 0): 1, ("did", 1): 2, ("did", 2): 3, ("did", 3): 4,
         ("did", 1, "elsewhere"): 9}, disks)
    assert held == {("did", 0): 4, ("did", 1): 3, ("did", 2): 2,
                    ("did", 3): 0, ("did", 1, "elsewhere"): 0}


def test_reference_store_imports_nothing_of_the_program():
    with open(reference_store.__file__) as fh:
        source = fh.read()
    assert "import plenum_tpu" not in source \
        and "from plenum_tpu" not in source
    assert sys.modules[reference_store.__name__].__doc__
