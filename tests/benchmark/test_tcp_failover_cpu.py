"""The `tcp_failover` launcher at test size on the CPU: a sound run (the
master primary SIGKILLed a third of the way into the window, one view
change, the victim read off its disk, restarted and caught up, the
comparisons over all four, liveness) comes out correct and leaves nothing
running; the control without the kill and the control that kills a
non-primary come out as they must. Labelled rehearsals: no metric is
written. No assertion rests on which batch a disk happened to hold at the
kill, on how many batches were in flight, or on which survivor voted
first."""
import asyncio
import json
import os
import signal
import uuid

import pytest

import bench_paths  # noqa: F401
from benchmarks import cell, manifest, reaper, readers
from benchmarks.topologies import tcp_failover

CELL = "tcp_failover.primary_kill"
SECONDS = 6.0
LIMIT_S = 240           # a run takes ~30 s alone, ~60 s three at a time


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
    # unique over concurrent pytest runs too (their tmp_path names repeat)
    marker = f"failovertest{os.getpid()}{uuid.uuid4().hex[:8]}"
    monkeypatch.setenv(reaper.MARKER_VAR, marker)
    result = tmp_path / "result.json"
    rc = cell.main(["--workload", CELL, "--seed", "2147483659",
                    "--seconds", str(SECONDS), "--trace", "0",
                    "--rehearse-cpu", "--run-dir", str(tmp_path),
                    "--result", str(result)])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert reaper.marked(marker) == []          # nothing left running
    compared = {x["compared"]["check"]: x["compared"]
                for x in lines if "compared" in x}
    return rc, json.loads(result.read_text()), lines, compared


@pytest.mark.usefixtures("time_limit")
def test_primary_killed_in_the_window_and_a_sound_run_is_correct(
        tmp_path, capsys, monkeypatch):
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is True and "rehearsal" in got, compared
    assert got["attempted"] > 0 and got["failed"] == 0 and got["metrics"] == {}
    assert all(r["ok"] for r in compared.values()), compared

    fault = next(x["fault"] for x in lines if "fault" in x)
    assert fault["victim"] == "Node1" and fault["victim_was_primary"]
    assert 0.30 * SECONDS <= fault["kill_offset_s"] <= 0.40 * SECONDS
    assert fault["connections_left"] == ["Node2", "Node3", "Node4"]
    # nobody was served for about the detection wait; the gap began at
    # the kill, not before it
    assert fault["reply_gap_s"] > 1.0
    assert fault["reply_gap_began_at_s"] >= fault["kill_offset_s"] - 0.5
    for check, limit in (("fault.kill_share_of_window", [0.3, 0.4]),
                         ("fault.victim_was_primary", 1),
                         ("failover.survivors_ordering_in_a_new_view", 3),
                         ("failover.victim_disk_txns_differing_from_survivors",
                          0), ("rejoin.restart_s", 120),
                         ("durable.post_restart_writes_acknowledged", 16),
                         ("nodes.distinct_views", 1),
                         ("reference.root_mismatches", 0),
                         ("ledger.acknowledged_writes_lost", 0)):
        assert compared[check]["limit"] == limit and compared[check]["ok"]

    survivors = [x["failover"] for x in lines if "failover" in x]
    assert [s["node"] for s in survivors] == ["Node2", "Node3", "Node4"]
    for s in survivors:
        vc = s["view_change"]
        # one view change is owed; a pool starved of CPU may add one of
        # its own (1 rehearsal in ~70 changed its view with no fault)
        assert s["view_no"] >= 1 and s["last_ordered_3pc"][0] >= 1
        assert vc["started"] >= 1 and vc["completed"] >= 1
        assert not vc["in_progress"]
        # a survivor starved of CPU may join on the others' votes before
        # it has noticed the closed connection itself: the protocol's two
        # phases are on every survivor, the detection wait on the voters
        assert {"start_to_new_view", "new_view_to_order"} \
            <= set(vc["last"]["phases_s"])
        assert vc["ordering"]["view_no"] >= 1
        assert vc["ordering"]["reverted_batches"] >= 0
        assert vc["ordering"]["waiting_at_first_cut"] > 0

    # f+1 of them voted, each PRIMARY_DISCONNECT_TIMEOUT after it saw the
    # primary's connection close (the configuration's setting: the floor)
    waits = [s["view_change"]["last"]["phases_s"]["detect_to_vote"]
             for s in survivors
             if "detect_to_vote" in s["view_change"]["last"]["phases_s"]]
    assert waits and min(waits) >= 1.5

    on_disk = next(x["on_disk"] for x in lines if "on_disk" in x)
    assert on_disk["domain_txns_on_its_disk"] > on_disk["preload"] == 4097
    rejoin = next(x["rejoin"] for x in lines if "rejoin" in x)
    assert rejoin["victim"] == "Node1" and rejoin["engine"] == "native"
    assert rejoin["catchup_txns"] > 0
    assert rejoin["recovery"]["rejoined"]["last_3pc"][0] >= 1
    # the storage counters were read from a survivor over the whole window
    counters = next(x["counters"] for x in lines if "counters" in x)
    assert counters["after"]["storage.domain_txns"] \
        - counters["before"]["storage.domain_txns"] >= got["attempted"]
    numbers = next(x["numbers"] for x in lines if "rehearsal" in x)
    assert {"commit_latency_p50_ms", "commit_latency_p95_ms",
            "committed_writes_per_s", "setup_s"} <= set(numbers)
    # the gap holds more than 5 % of the window's writes: p95 reads it
    assert numbers["commit_latency_p95_ms"]["value"] > 500.0


@pytest.mark.usefixtures("time_limit")
def test_control_without_the_kill_is_not_correct(tmp_path, capsys,
                                                 monkeypatch):
    monkeypatch.setattr(tcp_failover.Launcher, "kill_victim",
                        lambda self: None)
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is False and got["failed"] == 0
    failed = {c for c, r in compared.items() if not r["ok"]}
    # no kill was sent: that alone decides. (The survivors' check fails
    # too unless the starved pool changed its view on its own.)
    assert {"fault.kill_share_of_window", "nodes.fallback_problems"} \
        <= failed <= {"fault.kill_share_of_window",
                      "failover.survivors_ordering_in_a_new_view",
                      "nodes.fallback_problems"}, compared
    assert compared["fault.kill_share_of_window"]["got"] is None
    assert not any("rejoin" in x for x in lines)


@pytest.mark.usefixtures("time_limit")
def test_control_killing_a_non_primary_is_not_correct(tmp_path, capsys,
                                                      monkeypatch):
    monkeypatch.setattr(tcp_failover.Launcher, "pick_victim",
                        lambda self, primary: "Node4")
    rc, got, lines, compared = run_cell(tmp_path, capsys, monkeypatch)
    assert rc == 0 and got["correct"] is False and got["failed"] == 0
    failed = {c for c, r in compared.items() if not r["ok"]}
    # the victim was no primary: that alone decides
    assert {"fault.victim_was_primary", "nodes.fallback_problems"} \
        <= failed <= {"fault.victim_was_primary",
                      "failover.survivors_ordering_in_a_new_view",
                      "nodes.fallback_problems"}, compared
    assert compared["fault.kill_share_of_window"]["ok"]
    # no view change is owed, and the primary is among the survivors
    survivors = [x["failover"] for x in lines if "failover" in x]
    assert [s["node"] for s in survivors] == ["Node1", "Node2", "Node3"]
    # the restarted non-primary still converges with the others
    assert compared["nodes.distinct_views"]["ok"]
    assert compared["failover.victim_disk_txns_differing_from_survivors"]["ok"]
    rejoin = next(x["rejoin"] for x in lines if "rejoin" in x)
    assert rejoin["victim"] == "Node4"


# --- the pieces ---------------------------------------------------------------


def test_longest_gap_is_between_acknowledgements_inside_the_window():
    acked = {"a": 9.0, "b": 10.5, "c": 11.0, "d": 13.5, "e": 14.0,
             "late": 40.0}
    gap = tcp_failover.longest_gap(acked, 10.0, 20.0)
    assert gap == {"reply_gap_s": 2.5, "reply_gap_began_at_s": 1.0}
    assert tcp_failover.longest_gap({"a": 11.0}, 10.0, 20.0) == {
        "reply_gap_s": None, "reply_gap_began_at_s": None}


def test_client_drops_a_closed_connection_and_redials_it():
    """One of two servers goes away under the client: its connection is
    dropped, writes and flushes go on to the other, and `redial` brings
    it back."""
    from plenum_tpu.common.serialization import unpack

    class Req:
        def to_dict(self):
            return {"op": "x"}

    async def main():
        got = {"A": [], "B": []}
        writers = {"A": [], "B": []}

        def handler(name):
            async def serve(reader, writer):
                writers[name].append(writer)
                try:
                    while True:
                        hdr = await reader.readexactly(4)
                        got[name].append(unpack(await reader.readexactly(
                            int.from_bytes(hdr, "big"))))
                except (asyncio.IncompleteReadError, OSError):
                    pass
            return serve
        servers = {n: await asyncio.start_server(handler(n), "127.0.0.1", 0)
                   for n in got}
        addrs = {n: s.sockets[0].getsockname()[:2]
                 for n, s in servers.items()}
        client = tcp_failover.SurvivingConnections(addrs)
        await client.connect()
        client.write(Req())
        await client.flush()
        writers["A"][0].transport.abort()       # a reset, as a SIGKILL gives
        for _ in range(200):
            if "A" not in client.conns:
                break
            await asyncio.sleep(0.01)
        assert sorted(client.conns) == ["B"]
        for _ in range(3):
            client.write(Req())
            await client.flush()                # raises nothing
        await client.redial("A")
        client.write(Req())
        await client.flush()
        await asyncio.sleep(0.05)
        assert sorted(client.conns) == ["A", "B"]
        assert len(got["B"]) == 5 and len(got["A"]) == 2
        await client.close()
        for s in servers.values():
            s.close()
    asyncio.run(main())


def test_the_deployment_is_the_durable_pools_but_for_the_fault():
    mine = manifest.cell(CELL)["config"]
    durable = manifest.cell("tcp_durable.write_steady")["config"]
    for key in ("nodes", "f", "protocol_instances", "kv", "kv_engine",
                "service", "service_rehearsal", "shapes", "sizes",
                "rehearsal_sizes", "trace_seconds", "reduced"):
        assert mine[key] == durable[key], key
    assert "crash" not in mine and mine["topology"] == "tcp_failover"
    extra = {"PRIMARY_DISCONNECT_TIMEOUT": 1.5, "NEW_VIEW_TIMEOUT": 30.0}
    assert mine["settings"] == dict(durable["settings"], **extra)
    from plenum_tpu.config import Config
    assert all(getattr(Config(), k) == v for k, v in extra.items())
    assert set(durable["guarantees"]) < set(mine["guarantees"])
    for kept in ("write_acknowledged_on", "nodes_converge",
                 "no_acknowledged_write_lost", "read_accepted_on",
                 "client_signatures", "reply_after_flush"):
        assert mine["guarantees"][kept] == durable["guarantees"][kept]
    assert {"served_through_failover", "no_fork_across_view_change",
            "victim_rejoins"} <= set(mine["guarantees"])
    assert set(durable["assumed"]) < set(mine["assumed"])
    assert any("ToleratePrimaryDisconnection is 60 s" in a
               for a in mine["assumed"])
    fault = mine["fault"]
    assert fault["victim"] == "master_primary" and fault["signal"] == "SIGKILL"
    assert abs(fault["at_share_of_window"] - 1 / 3) < 1e-9
    assert fault["kill_share_limits"] == [0.30, 0.40]
    assert "reads_from" in mine and "fault_is" in mine
    drive, control = manifest.cell(CELL)["workload"], manifest.cell(
        "tcp_durable.write_steady")["workload"]
    assert (drive["drive"], drive["rate_per_s"]) == (
        control["drive"], control["rate_per_s"]) == ("open_loop", 320)
    assert manifest.cell(CELL)["entry"]["traffic"] == "mixed_writes"


def test_the_cell_reports_the_durable_cells_metrics_and_five_of_its_own():
    mine = {m["name"] for m in manifest.benchmark()["per_layer"]
            if CELL in m.get("workloads", [])}
    durable = {m["name"] for m in manifest.benchmark()["per_layer"]
               if "tcp_durable.write_steady" in m.get("workloads", [])}
    own = {"failover.reply_gap_ms", "failover.detect_to_vote_ms",
           "failover.vote_to_new_view_ms", "failover.new_view_to_order_ms",
           "failover.view_changes"}
    assert mine == durable | own
    samples = {"failover.reply_gap_s": [1.9],
               "failover.detect_to_vote_s": [1.52, 1.50, 1.51],
               "failover.vote_to_new_view_s": [0.05, 0.2, 0.1],
               "failover.new_view_to_order_s": [0.3, 0.1, 0.2]}
    obs = {"samples": samples, "counters": {
        "before": {}, "after": {"failover.view_changes_started": 3,
                                "failover.survivors": 3}}}
    read = {name: readers.read(manifest.metric_spec("per_layer", name), obs)
            for name in own}
    assert read == {"failover.reply_gap_ms": pytest.approx(1900.0),
                    "failover.detect_to_vote_ms": pytest.approx(1510.0),
                    "failover.vote_to_new_view_ms": pytest.approx(100.0),
                    "failover.new_view_to_order_ms": pytest.approx(200.0),
                    "failover.view_changes": 1.0}
    assert {m["moves"] for m in manifest.benchmark()["per_layer"]
            if m["name"] in own} == {"commit_latency_p95_ms"}
