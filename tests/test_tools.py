"""Ops-surface tests: wallet, keygen, genesis files, and a real 4-process
pool started via the start_node script, written to and read from with the
PoolClient over TCP.

Reference test model: the scripts/ + client e2e flow (SURVEY.md §2 tools,
client wallet).
"""
from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- wallet ---------------------------------------------------------------

def test_wallet_sign_and_roundtrip(tmp_path):
    from plenum_tpu.client import Wallet
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    from plenum_tpu.execution.txn import NYM
    from plenum_tpu.utils.base58 import b58decode

    w = Wallet("w1")
    did = w.add_identifier(seed=b"wallet-seed-0001".ljust(32, b"\0"))
    assert w.default_id == did
    req = w.sign_request({"type": NYM, "dest": "X", "verkey": "Y"})
    assert req.identifier == did and req.signature
    ok = CpuEd25519Verifier().verify(
        req.signing_bytes(), b58decode(req.signature),
        b58decode(w.verkey_of(did)))
    assert ok

    # persistence: same keys come back
    path = str(tmp_path / "wallet.bin")
    w.save(path)
    assert oct(os.stat(path).st_mode & 0o777) == "0o600"
    w2 = Wallet.load(path)
    assert w2.identifiers() == [did] and w2.default_id == did
    assert w2.verkey_of(did) == w.verkey_of(did)


# --- keygen + genesis -----------------------------------------------------

def test_keygen_and_genesis_files(tmp_path):
    from plenum_tpu.common.node_messages import (DOMAIN_LEDGER_ID,
                                                 POOL_LEDGER_ID)
    from plenum_tpu.crypto.bls import verify_pop
    from plenum_tpu.tools import genesis as gen
    from plenum_tpu.tools import keygen

    base = str(tmp_path)
    for i, name in enumerate(("Alpha", "Beta")):
        keys = keygen.generate_keys(
            name, seed=(b"kg%d" % i).ljust(32, b"\0"))
        keygen.save_keys(keys, base)
        loaded = keygen.load_keys(base, name)
        assert loaded == keys
        assert verify_pop(keys["bls_pop"], keys["bls_pk"])

    out = gen.build_genesis_files(
        base, [("Alpha", "127.0.0.1", 9701, 9702),
               ("Beta", "127.0.0.1", 9703, 9704)],
        trustee_seed=b"t".ljust(32, b"\0"))
    assert os.path.exists(out["pool_genesis"])
    loaded = gen.load_genesis_files(base)
    assert len(loaded[POOL_LEDGER_ID]) == 2
    assert len(loaded[DOMAIN_LEDGER_ID]) == 1
    data = loaded[POOL_LEDGER_ID][0]["txn"]["data"]["data"]
    assert data["alias"] == "Alpha" and data["node_port"] == 9701


# --- 4 OS processes over real sockets -------------------------------------


@pytest.mark.slow
def test_four_process_pool_orders_nym(tmp_path):
    from plenum_tpu.client import PoolClient, Wallet
    from plenum_tpu.execution.txn import NYM
    from plenum_tpu.tools.tcp_pool import setup_pool_dir

    base = str(tmp_path)
    names = ["Node1", "Node2", "Node3", "Node4"]
    trustee_seed = b"proc-trustee".ljust(32, b"\0")
    specs = setup_pool_dir(base, names, trustee_seed)

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    procs = []
    try:
        for name in names:
            cmd = [sys.executable, "-m", "plenum_tpu.tools.start_node",
                   "--name", name, "--base-dir", base, "--kv", "memory"]
            if name == "Node1":
                cmd.append("--record")     # exercised by the replay below
            procs.append(subprocess.Popen(
                cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT))
        # wait for every process to report "started"
        for p in procs:
            line = p.stdout.readline()
            assert b"started" in line, line

        wallet = Wallet("cli")
        trustee_did = wallet.add_identifier(seed=trustee_seed)
        user_did = wallet.add_identifier(seed=b"proc-user".ljust(32, b"\0"))
        req = wallet.sign_request(
            {"type": NYM, "dest": user_did,
             "verkey": wallet.verkey_of(user_did)}, identifier=trustee_did)

        async def run():
            client = PoolClient(
                {name: ("127.0.0.1", spec[3])
                 for name, spec in zip(names, specs)}, f=1)
            try:
                return await client.submit(req, timeout=30.0)
            finally:
                await client.close()

        reply = asyncio.run(run())
        assert reply["op"] == "REPLY", reply
        txn = reply["result"]
        assert txn["txn"]["data"]["dest"] == user_did
        assert txn["txnMetadata"]["seqNo"] == 2

        # offline replay of the recorded node reproduces its ledger state
        # (STACK_COMPANION story: record in production, debug offline)
        procs[0].send_signal(signal.SIGTERM)
        procs[0].wait(timeout=5)
        out = subprocess.run(
            [sys.executable, "-m", "plenum_tpu.tools.replay",
             "--name", "Node1", "--base-dir", base],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        replayed = json.loads(out.stdout.strip().splitlines()[-1])
        from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID
        dom = replayed["ledgers"][str(DOMAIN_LEDGER_ID)] \
            if str(DOMAIN_LEDGER_ID) in replayed["ledgers"] \
            else replayed["ledgers"][DOMAIN_LEDGER_ID]
        assert dom["size"] == 2            # genesis NYM + the ordered one
        assert replayed["last_ordered_3pc"][1] >= 1
    finally:
        from plenum_tpu.tools.tcp_pool import stop_processes
        stop_processes(procs)


# --- the launcher's teardown (stub children, no pool) -----------------------

# traps SIGTERM and sleeps: only SIGKILL ends it. The line it prints
# says the handler is installed (and passes for a service's start line).
_DEAF_CHILD = ("import signal, time; "
               "signal.signal(signal.SIGTERM, lambda *a: None); "
               "print('{\"crypto_service\": {}, \"started\": 1}', "
               "flush=True); time.sleep(600)")
# says nothing and dies of SIGTERM, as a node that has not started yet
_MUTE_CHILD = "import time; time.sleep(600)"
# exits, with a last word, once the file named exists
_DYING_CHILD = ("import os, time\n"
                "while not os.path.exists({!r}): time.sleep(0.01)\n"
                "print('boom', flush=True)")


def _gone(pid: int) -> bool:
    """Not running: no such process, or a zombie whoever its parent is
    (a child this process reaped is the first; an orphan that pid 1 has
    not collected yet may be the second)."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as fh:
            return fh.read().rpartition(b")")[2].split()[0] in (b"Z", b"X")
    except (FileNotFoundError, ProcessLookupError):
        return True


@pytest.fixture
def stub_children(monkeypatch):
    """`tcp_pool` spawns `python -c <stub>` instead of nodes and services;
    everything else about the spawn is the launcher's own. -> (stubs: the
    queue of stub sources, spawned: [(Popen, its process group)])."""
    from plenum_tpu.tools import tcp_pool
    real_popen = subprocess.Popen
    stubs, spawned = [], []

    def popen(cmd, **kwargs):
        proc = real_popen([sys.executable, "-c", stubs.pop(0)], **kwargs)
        spawned.append((proc, os.getpgid(proc.pid)))
        return proc

    monkeypatch.setattr(tcp_pool.subprocess, "Popen", popen)
    monkeypatch.setattr(tcp_pool, "STOP_GRACE_S", 0.5)
    yield stubs, spawned
    for proc, _pgid in spawned:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_stop_processes_kills_and_reaps_a_child_deaf_to_sigterm(
        stub_children):
    from plenum_tpu.tools import tcp_pool
    stubs, _ = stub_children
    stubs.append(_DEAF_CHILD)
    proc = tcp_pool._spawn(["a", "node"], stdout=subprocess.PIPE)
    assert b"started" in proc.stdout.readline()     # the trap is set
    t0 = time.monotonic()
    tcp_pool.stop_processes([proc, None])
    assert proc.returncode == -signal.SIGKILL
    assert time.monotonic() - t0 < 5.0
    with pytest.raises(ProcessLookupError):         # reaped, not a zombie
        os.kill(proc.pid, 0)
    proc.stdout.close()
    tcp_pool.stop_processes([proc])                 # a no-op on the dead


# what chip_smoke's `spawn_phase` makes of a phase: a launcher in a group
# of its own that starts children through `tcp_pool`, says who they are,
# and then never gets to its `finally`
_LAUNCHER = ("import json, subprocess, sys, time\n"
             "from plenum_tpu.tools import tcp_pool\n"
             "kids = [tcp_pool._spawn([sys.executable, '-c', sys.argv[1]],\n"
             "                        stdout=subprocess.PIPE)\n"
             "        for _ in range(2)]\n"
             "for kid in kids: kid.stdout.readline()\n"
             "print(json.dumps([kid.pid for kid in kids]), flush=True)\n"
             "time.sleep(600)")


def test_killing_the_launchers_group_leaves_no_child():
    """A caller that cannot ask the launcher to stop (a phase past its
    deadline, a hung test) SIGKILLs the launcher's group; the nodes and
    the service that holds the chip must be in it."""
    launcher = subprocess.Popen(
        [sys.executable, "-c", _LAUNCHER, _DEAF_CHILD], cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO), stdout=subprocess.PIPE,
        start_new_session=True)
    kids = []
    try:
        kids = json.loads(launcher.stdout.readline())
        assert len(kids) == 2
    finally:
        os.killpg(launcher.pid, signal.SIGKILL)
        launcher.wait()
        launcher.stdout.close()
    deadline = time.monotonic() + 5.0
    while not all(map(_gone, kids)) and time.monotonic() < deadline:
        time.sleep(0.01)
    survivors = [pid for pid in kids if not _gone(pid)]
    for pid in survivors:               # a failure must not leak them
        os.kill(pid, signal.SIGKILL)
    assert survivors == []


def test_run_tcp_pool_leaves_no_child_when_a_node_dies_starting(
        stub_children, tmp_path):
    pytest.importorskip(
        "cryptography",
        reason="setup_pool_dir's keygen needs cryptography")
    from plenum_tpu.tools import tcp_pool
    stubs, spawned = stub_children
    # the third node dies only after the second has set its trap
    trap_set = str(tmp_path / "trap_set")
    stubs.extend([
        _MUTE_CHILD,
        _DEAF_CHILD.replace("time.sleep(600)",
                            f"open({trap_set!r}, 'w').close(); "
                            "time.sleep(600)"),
        _DYING_CHILD.format(trap_set), _MUTE_CHILD])
    with pytest.raises(RuntimeError, match="exited before starting"):
        tcp_pool.run_tcp_pool(n_nodes=4, backend="cpu",
                              base_dir=str(tmp_path))
    assert len(spawned) == 4
    for proc, pgid in spawned:
        assert pgid == os.getpgid(0)    # one SIGKILL of the group ends all
        assert proc.returncode is not None
        with pytest.raises(ProcessLookupError):
            os.kill(proc.pid, 0)
    # TERM ended the mute ones, KILL the deaf one, the third by itself
    assert [p.returncode for p, _pgid in spawned] == [
        -signal.SIGTERM, -signal.SIGKILL, 0, -signal.SIGTERM]


@pytest.mark.slow
def test_tcp_pool_bench_orders_load():
    """The real-transport benchmark drives a 4-process TCP pool end to end:
    every request reaches an f+1 REPLY quorum over the wire."""
    from plenum_tpu.tools.tcp_pool import run_tcp_pool
    stats = run_tcp_pool(n_nodes=4, n_txns=30, timeout=90.0)
    assert stats["txns_ordered"] == 30, stats
    assert stats["tps"] > 0
    assert stats["p50_latency_ms"] is not None


def test_pipelined_client_survives_dead_node_and_reuse():
    """The pipelined client must tolerate an unreachable node (quorum
    covers it) and be reusable across drive() calls with a clean slate."""
    from plenum_tpu.client import PipelinedPoolClient
    from plenum_tpu.common.request import Request
    from plenum_tpu.common.serialization import pack, unpack

    async def main():
        async def serve(reader, writer):
            try:
                while True:
                    hdr = await reader.readexactly(4)
                    frame = await reader.readexactly(
                        int.from_bytes(hdr, "big"))
                    req = unpack(frame)
                    reply = pack({"op": "REPLY", "result": {"txn": {
                        "metadata": {"from": req["identifier"],
                                     "reqId": req["reqId"]}}}})
                    writer.write(len(reply).to_bytes(4, "big") + reply)
                    await writer.drain()
            except (asyncio.IncompleteReadError, OSError):
                return

        servers = [await asyncio.start_server(serve, "127.0.0.1", 0)
                   for _ in range(3)]
        addrs = {f"N{i}": ("127.0.0.1", s.sockets[0].getsockname()[1])
                 for i, s in enumerate(servers)}
        addrs["Ndead"] = ("127.0.0.1", 1)      # nothing listens there

        client = PipelinedPoolClient(addrs, f=1)
        reqs = [Request("idr", i, {"type": "1"}) for i in range(5)]
        done, _ = await client.drive(reqs, window=3, timeout=10.0)
        assert len(done) == 5

        # reuse: a smaller second batch must NOT be satisfied by stale state
        reqs2 = [Request("idr", 100 + i, {"type": "1"}) for i in range(2)]
        done2, _ = await client.drive(reqs2, window=2, timeout=10.0)
        assert set(done2) == {("idr", 100), ("idr", 101)}
        for s in servers:
            s.close()

    asyncio.run(main())


def test_metrics_report_reads_flushed_history(tmp_path):
    """tools.metrics_report turns a node's flushed metrics store into
    per-metric folds and a derived summary (ref scripts/process_logs)."""
    from plenum_tpu.common.metrics import KvMetricsCollector, MetricsName
    from plenum_tpu.storage.kv_file import KvFile
    from plenum_tpu.tools.metrics_report import main as report_main, report_node

    mdir = tmp_path / "Node1" / "metrics"
    clock = [1000.0]
    m = KvMetricsCollector(KvFile(str(mdir)), now=lambda: clock[0])
    for tick in range(3):
        for _ in range(10):
            m.add_event(MetricsName.ORDERED_BATCH_SIZE, 5)
        m.add_event(MetricsName.PREPARE_PHASE_TIME, 0.040)
        m.add_event(MetricsName.CLIENT_INBOX_DEPTH, tick)  # gauge: last wins
        m.flush()
        clock[0] += 10.0

    folds, summary = report_node(str(mdir), last_s=None)
    assert folds["node.ordered_batch_size"]["count"] == 30
    assert summary["txns_ordered"] == 150
    assert summary["window_s"] == 20.0            # 3 flushes, 10 s apart
    assert summary["tps"] == 7.5                  # 150 txns / 20 s
    assert summary["prepare_phase_ms"] == 40.0
    assert summary["client_inbox_depth_max"] == 2

    # the trailing-window filter drops the first flush
    _, tail = report_node(str(mdir), last_s=10.0)
    assert tail["txns_ordered"] == 100

    # CLI over the whole base dir, machine-readable
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = report_main([str(tmp_path), "--json"])
    assert rc == 0
    out = json.loads(buf.getvalue())
    assert out["Node1"]["summary"]["txns_ordered"] == 150


def test_metrics_report_commit_stage_percentiles(tmp_path):
    """Commit-path stage timers flush bounded RAW samples so the report
    can print honest p50/p95 per stage (not just fold means), plus the
    pairings-per-batch and plane-dispatch counters that previously never
    reached the report."""
    from plenum_tpu.common.metrics import KvMetricsCollector, MetricsName
    from plenum_tpu.storage.kv_file import KvFile
    from plenum_tpu.tools.metrics_report import report_node

    mdir = tmp_path / "Node1" / "metrics"
    m = KvMetricsCollector(KvFile(str(mdir)), now=lambda: 1000.0)
    for i in range(100):
        m.add_event(MetricsName.COMMIT_BLS_VERIFY_TIME, 0.001 * (i + 1))
        m.add_event(MetricsName.COMMIT_DURABLE_TIME, 0.002)
        m.add_event(MetricsName.BLS_PAIRINGS_PER_BATCH, 2)
    m.add_event(MetricsName.GROUP_COMMIT_BATCHES, 3)
    m.add_event(MetricsName.SIG_PLANE_DISPATCHES, 7)   # cumulative gauge
    m.add_event(MetricsName.BLS_PAIRING_CHECKS, 100)
    m.add_event(MetricsName.BLS_PAIRINGS, 200)
    m.flush()

    folds, summary = report_node(str(mdir), last_s=None)
    assert summary["bls_verify_ms_p50"] == pytest.approx(51.0, abs=2.0)
    assert summary["bls_verify_ms_p95"] == pytest.approx(96.0, abs=2.0)
    assert summary["durable_ms_p50"] == pytest.approx(2.0, abs=0.1)
    assert summary["pairings_per_batch"] == 2.0
    assert summary["pairing_checks_total"] == 100
    assert summary["pairings_total"] == 200
    assert summary["plane_dispatches"] == 7
    assert summary["group_commit_batches_mean"] == 3.0
    assert "batch_cuts" not in summary     # no cut recorded, no section


def test_metrics_report_prints_the_stage_clock(tmp_path):
    """The eight stage names come out beside the commit-path figures:
    count and mean from the per-request weighted fold (a batch's span once
    a request it carries), quantiles from the samples (one a batch)."""
    from plenum_tpu.common.metrics import KvMetricsCollector, MetricsName
    from plenum_tpu.storage.kv_file import KvFile
    from plenum_tpu.tools.metrics_report import report_node

    mdir = tmp_path / "Node1" / "metrics"
    m = KvMetricsCollector(KvFile(str(mdir)), now=lambda: 1000.0)
    for i in range(10):
        m.add_event(MetricsName.STAGE_INBOX_WAIT, 0.002)
        m.add_event(MetricsName.STAGE_RESIDENCE, 0.100)
    m.add_event(MetricsName.STAGE_ORDERING_WAIT, 0.040, 8)  # a batch of 8
    m.add_event(MetricsName.STAGE_ORDERING_WAIT, 0.060, 2)
    m.flush()
    _, summary = report_node(str(mdir), last_s=None)
    stages = summary["stages"]
    assert set(stages) == {"inbox_wait", "ordering_wait", "residence"}
    assert stages["inbox_wait"] == {"count": 10, "mean_ms": 2.0,
                                    "p50_ms": 2.0, "p95_ms": 2.0}
    assert stages["ordering_wait"]["count"] == 10
    assert stages["ordering_wait"]["mean_ms"] == 44.0   # (8*40 + 2*60) / 10
    assert stages["ordering_wait"]["p95_ms"] == 60.0    # of the 2 samples


def test_metrics_report_batch_cut_reasons(tmp_path):
    """The master primary's cut-reason counts are cumulative gauges, one
    event per cut: the report reads each back as its latest (max) value."""
    from plenum_tpu.common.metrics import KvMetricsCollector
    from plenum_tpu.consensus.batch_controller import CUT_METRICS
    from plenum_tpu.storage.kv_file import KvFile
    from plenum_tpu.tools.metrics_report import report_node

    mdir = tmp_path / "Node1" / "metrics"
    m = KvMetricsCollector(KvFile(str(mdir)), now=lambda: 1000.0)
    for n in range(1, 41):
        m.add_event(CUT_METRICS["idle"], n)
    m.add_event(CUT_METRICS["timeout"], 1)
    m.flush()
    for n in range(41, 46):
        m.add_event(CUT_METRICS["idle"], n)
    m.flush()
    _, summary = report_node(str(mdir), last_s=None)
    assert summary["batch_cuts"] == {"full": 0, "idle": 45, "timeout": 1,
                                     "forced": 0}


# the first is the retired per-call A/B arm's name
@pytest.mark.parametrize("backend", ["jax-percall", "tpu"])
def test_local_pool_rejects_a_backend_it_does_not_build(backend):
    """Such a string used to fall through to the CPU verifier and label
    the run with the name it was given."""
    import plenum_tpu.tools.local_pool as lp
    with pytest.raises(ValueError, match="cpu, jax"):
        lp.build_pool(4, backend)
    with pytest.raises(SystemExit):
        lp.main(["--backend", backend])
    assert lp.build_pool(4, "cpu").pipeline is None


def test_distinct_signers_config_orders_owner_writes():
    """n distinct client keys on the authN hot path: phase 1 creates n
    DIDs (trustee-signed NYMs), phase 2 has every DID owner-sign an
    ATTRIB on itself (authorization: owner-or-trustee), so the traffic
    is diverse-client, not one amortized trustee key."""
    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.common.request import Request
    from plenum_tpu.execution.txn import ATTRIB
    n = 40
    pool = lp.build_pool(4, "cpu")
    nyms, users = lp.signed_nyms(pool.trustee, n, tag=b"ds")
    first_reply, _, _ = lp.drive(pool, nyms, timeout=60.0)
    assert len(first_reply) == n, "setup incomplete"
    attribs = []
    for i, u in enumerate(users):
        req = Request(u.identifier, 1,
                      {"type": ATTRIB, "dest": u.identifier,
                       "raw": json.dumps({"endpoint": str(i)})})
        req.signature = u.sign_b58(req.signing_bytes())
        attribs.append(req)
    assert len({r.identifier for r in attribs}) == n
    first_reply, _, _ = lp.drive(pool, attribs, timeout=60.0)
    assert len(first_reply) == n
    assert lp.pool_roots(pool)["agree"]
    assert {pool.nodes[name].c.db.get_ledger(pool.domain_ledger_id).size
            for name in pool.names} == {1 + 2 * n}


@pytest.mark.parametrize("stage_samples", [True, False],
                         ids=["stage_clock_sampling", "null_collector"])
def test_replay_reproduces_span_sequence(stage_samples):
    """Record/replay x tracing determinism guard: replaying a recorded
    node under the mock clock reproduces a BYTE-IDENTICAL span sequence.
    Span timestamps come only from the injectable timer and payloads only
    from message content (wall_durations=False strips the perf_counter
    stage durations, the one legitimately non-deterministic field), so
    any divergence here means a span site leaked wall state into the
    trace — the property the flight-recorder postmortems rely on. The
    span sites go through the stage clock, whose perf_counter durations
    land on the metrics store and never in the ring: the replayed node
    gives the same bytes whether its clock samples or feeds a
    NullMetricsCollector."""
    from plenum_tpu.common.metrics import (MetricsCollector,
                                           NullMetricsCollector)
    from plenum_tpu.common.event_bus import ExternalBus
    from plenum_tpu.common.timer import MockTimer
    from plenum_tpu.common.tracing import Tracer, span_sequence
    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.network import SimNetwork, SimRandom
    from plenum_tpu.node import Node, NodeBootstrap
    from plenum_tpu.node.recorder import Recorder, attach_recorder, replay
    from plenum_tpu.storage.kv_memory import KvMemory
    from test_pool import NODES, make_genesis, signed_nym

    genesis, trustee = make_genesis(NODES)
    timer = MockTimer()
    net = SimNetwork(timer, SimRandom(11))
    config = Config(Max3PCBatchWait=0.05)
    recorder = Recorder(KvMemory(), now=timer.get_current_time)
    nodes = {}
    for name in NODES:
        bus = net.create_peer(name)
        components = NodeBootstrap(name, genesis_txns=genesis).build()
        tracer = Tracer(name, timer.get_current_time,
                        wall_durations=False) if name == "Alpha" else None
        nodes[name] = Node(name, timer, bus, components, config=config,
                           tracer=tracer)
        if name == "Alpha":
            # before connect_all: the Connected events must be recorded
            attach_recorder(nodes[name], recorder)
    net.connect_all()

    user = Ed25519Signer(seed=b"replay-span-user".ljust(32, b"\0"))
    req = signed_nym(trustee, user, 1)
    for name in NODES:
        nodes[name].handle_client_message(req.to_dict(), "cli")
    for _ in range(100):
        for node in nodes.values():
            node.prod()
        timer.advance(0.05)
    live = span_sequence(nodes["Alpha"].tracer.snapshot())
    assert b'"ordered"' in live and b'"reply"' in live
    assert b'"queued"' in live          # the inbox stage's ring event
    assert nodes["Alpha"].stages.report()["stage.residence"]["count"] == 1

    # fresh Alpha from the same genesis; feed the recorded stream back
    first_ts = next(ts for ts, *_ in recorder.iter_records())
    timer2 = MockTimer(start=first_ts)
    bus2 = ExternalBus(send_handler=lambda msg, dst: None)
    components2 = NodeBootstrap("Alpha", genesis_txns=genesis).build()
    tracer2 = Tracer("Alpha", timer2.get_current_time,
                     wall_durations=False)
    metrics2 = MetricsCollector() if stage_samples else NullMetricsCollector()
    node2 = Node("Alpha", timer2, bus2, components2, config=config,
                 tracer=tracer2, metrics=metrics2)
    replay(recorder.iter_records(), node2, timer2)
    assert span_sequence(tracer2.snapshot()) == live
    assert ("stage.residence" in metrics2.accumulators) == stage_samples


def test_log_analyzer_unit(tmp_path):
    """Analyzer halves: error clustering over text, per-view timeline
    over structured events (ref scripts/process_logs redesign)."""
    import json as _json
    from plenum_tpu.tools.log_analyzer import analyze_node
    d = tmp_path / "NodeX"
    d.mkdir()
    (d / "node.log").write_text(
        "2026-01-01 WARNING stack undecodable message from Node2\n"
        "2026-01-01 WARNING stack undecodable message from Node3\n"
        "2026-01-01 ERROR svc handler failed for PrePrepare 17 from Node4\n"
        "plain info noise that must be ignored\n"
        "2026-01-01 ERROR svc handler failed for PrePrepare 99 from Node4\n")
    rows = [
        {"t": 10.0, "event": "restored_from_audit", "data": [0, 0]},
        {"t": 11.0, "event": "suspicion", "data": [21, "Node1"]},
        {"t": 12.5, "event": "vc_stall_phases",
         "data": {"detect": 11.0, "vote": 12.5, "start": 12.56,
                  "new_view": 12.58, "order": 12.9}},
        {"t": 13.0, "event": "view_change_complete", "data": 1},
        {"t": 14.0, "event": "catchup_started", "data": None},
    ]
    with open(d / "events.jsonl", "w") as fh:
        for r in rows:
            fh.write(_json.dumps(r) + "\n")
        fh.write('{"t": 15.0, "event": "torn')   # torn tail: tolerated
    rep = analyze_node(str(d))
    assert rep["event_counts"]["suspicion"] == 1
    # two clusters: the repeated undecodable (x2) and the failed handler
    # (x2, seq-no digits normalized into one template)
    levels = {(c["level"], c["count"]) for c in rep["error_clusters"]}
    assert levels == {("WARNING", 2), ("ERROR", 2)}
    views = rep["views"]
    assert [v["view_no"] for v in views] == [0, 1]
    assert views[0]["vc_stall"]["total_s"] == 1.9
    assert views[0]["vc_stall"]["phases"]["order"] == 1.9
    assert views[1]["events"] == {"catchup_started": 1}


def test_durable_spylog_survives_torn_tail(tmp_path):
    """Crash mid-write tears a line; the restarted log starts on a fresh
    line and the analyzer skips ONLY the torn line (review findings)."""
    from plenum_tpu.tools.log_analyzer import read_events
    from plenum_tpu.tools.start_node import _DurableSpylog
    p = str(tmp_path / "events.jsonl")
    log = _DurableSpylog(p, now=lambda: 1.0)
    log.append(("view_change_complete", 1))
    log._fh.close()
    with open(p, "a") as fh:
        fh.write('{"t": 2.0, "event": "torn')      # crash mid-write
    log2 = _DurableSpylog(p, now=lambda: 3.0)      # restart
    log2.append(("catchup_started", None))
    log2._fh.close()
    rows = read_events(p)
    assert [r["event"] for r in rows] == ["view_change_complete",
                                          "catchup_started"]


def test_start_node_chunked_backend_is_durable(tmp_path):
    """--kv chunked must build a node on KvChunked ledgers (review
    finding: it silently fell back to in-memory storage)."""
    pytest.importorskip(
        "cryptography",
        reason="build_node stands up the TCP stack, which needs cryptography")
    from plenum_tpu.storage.kv_chunked import KvChunked
    from plenum_tpu.tools.start_node import build_node
    from plenum_tpu.tools.tcp_pool import setup_pool_dir
    base = str(tmp_path)
    setup_pool_dir(base, ["N1", "N2", "N3", "N4"], b"t" * 32)
    prodable, node, _reg = build_node("N1", base, kv="chunked")
    lid = 1
    log = node.c.db.get_ledger(lid)._log
    assert isinstance(log, KvChunked), type(log)
    node.c.db.close()


# --- the documents describe the tree that exists ---------------------------

def test_docs_name_only_what_exists():
    """Every file and module README.md and docs/*.md name is there: a
    `dir/name.py` path resolves against the root or `plenum_tpu/`, a bare
    `name.py` is some file's name in the tree, and a `python -m
    plenum_tpu.x` module can be run."""
    import pathlib
    import re
    repo = pathlib.Path(REPO)
    tree = {p.name for top in ("plenum_tpu", "tests", "benchmarks", "probes",
                               "baseline")
            for p in (repo / top).rglob("*.py")}
    tree |= {p.name for p in repo.glob("*.py")}
    missing = []
    for doc in [repo / "README.md", *sorted((repo / "docs").glob("*.md"))]:
        text = doc.read_text()
        for m in re.finditer(r"(?<![\w/.])((?:\w+/)*)(\w+\.py)\b", text):
            path = m.group(1) + m.group(2)
            found = ((repo / path).is_file()
                     or (repo / "plenum_tpu" / path).is_file()
                     if m.group(1) else m.group(2) in tree)
            if not found:
                missing.append((doc.name, path))
        for m in re.finditer(r"python3? -m (plenum_tpu(?:\.\w+)+)", text):
            rel = m.group(1).replace(".", "/")
            if not ((repo / f"{rel}.py").is_file()
                    or (repo / rel / "__main__.py").is_file()):
                missing.append((doc.name, m.group(0)))
    assert missing == []
