"""Real-transport pool benchmark: N OS processes over TCP under write load.

The in-process benchmark (tools/local_pool.py) measures the consensus
pipeline over the deterministic sim fabric; THIS tool stands up the same
pool the way an operator would — keygen + genesis + one start_node process
per validator, authenticated-encrypted TCP between them (network/tcp_stack)
— and drives pre-signed NYM writes through the client ports with a
pipelined streaming client, reporting wall-clock TPS and commit latency.
This is the framework's analog of benchmarking the reference's
scripts/start_plenum_node x4 localhost pool.

    python -m plenum_tpu.tools.tcp_pool --nodes 4 --txns 200 [--json]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRUSTEE_SEED = b"tcp-pool-trustee".ljust(32, b"\0")
# what a child gets between SIGTERM and SIGKILL: a node flushes its
# metrics tail and closes its ring in well under this
STOP_GRACE_S = 5.0


def _spawn(cmd: list, **kwargs) -> subprocess.Popen:
    """Every child stays in the launcher's process group and session: a
    caller that has to end a launcher it cannot ask (chip_smoke's phase
    deadline, a test runner's timeout) SIGKILLs that one group, and no
    node or chip-owning service outlives the launcher that started it."""
    return subprocess.Popen(cmd, cwd=REPO, **kwargs)


def stop_processes(procs) -> None:
    """THE teardown: SIGTERM every live child, give them STOP_GRACE_S
    between them, SIGKILL each that is still there, and reap every one —
    nothing is left running and nothing is left a zombie. Entries may be
    None (a service that was never started)."""
    live = [p for p in procs if p is not None and p.poll() is None]
    for p in live:
        p.send_signal(signal.SIGTERM)
    deadline = time.monotonic() + STOP_GRACE_S
    for p in live:
        try:
            p.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def setup_pool_dir(base: str, names: list[str], trustee_seed: bytes):
    """keygen + genesis files for a localhost pool -> port specs."""
    from plenum_tpu.tools import genesis as gen
    from plenum_tpu.tools import keygen

    ports = _free_ports(2 * len(names))
    specs = []
    for i, name in enumerate(names):
        keygen.save_keys(keygen.generate_keys(
            name, seed=(b"tcppool%d" % i).ljust(32, b"\0")), base)
        specs.append((name, "127.0.0.1", ports[2 * i], ports[2 * i + 1]))
    gen.build_genesis_files(base, specs, trustee_seed)
    return specs


def _wait_all_started(procs, deadline_s: float) -> None:
    """Wait (bounded!) for every child to print its "started" line — a
    wedged child must fail the bench, never hang it."""
    import selectors
    deadline = time.perf_counter() + deadline_s
    sel = selectors.DefaultSelector()
    pending = {}
    for p in procs:
        os.set_blocking(p.stdout.fileno(), False)
        sel.register(p.stdout, selectors.EVENT_READ, p)
        pending[p.stdout.fileno()] = b""
    try:
        while pending:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                raise RuntimeError(
                    f"{len(pending)} node(s) never reported 'started'")
            for key, _ in sel.select(timeout=remaining):
                fd = key.fileobj.fileno()
                chunk = key.fileobj.read() or b""
                buf = pending[fd] + chunk
                if b"started" in buf:
                    sel.unregister(key.fileobj)
                    del pending[fd]
                elif key.data.poll() is not None:
                    raise RuntimeError(
                        f"node exited before starting: {buf!r}")
                else:
                    pending[fd] = buf
    finally:
        sel.close()
        for p in procs:
            if p.poll() is None:
                os.set_blocking(p.stdout.fileno(), True)


async def drive_load(addrs, f, requests, window: int, timeout: float):
    """-> (done {key: t_done}, submit_times {key: t_sent})."""
    from plenum_tpu.client.pipelined import PipelinedPoolClient
    client = PipelinedPoolClient(addrs, f)
    return await client.drive(requests, window=window, timeout=timeout)


def _start_crypto_service(inner: str, sock_path: str, min_batch: int,
                          env: dict) -> tuple:
    """Spawn the crypto-plane owner process and wait (bounded) for the
    line it prints once its socket is bound. -> (proc, start line dict).
    For a jax inner the line's "device" is what JAX gave that process.
    Output goes to a log beside the socket, so a chatty backend can never
    fill a pipe nobody reads and stall the plane."""
    log_path = sock_path + ".log"

    def tail() -> str:
        with open(log_path, "rb") as fh:
            return fh.read().decode(errors="replace")[-2000:]

    with open(log_path, "wb") as log:
        proc = _spawn(
            [sys.executable, "-m", "plenum_tpu.parallel.crypto_service",
             "--socket", sock_path, "--backend", inner,
             "--min-batch", str(min_batch)],
            env=env, stdout=log, stderr=subprocess.STDOUT)
    deadline = time.perf_counter() + 240.0      # backend init included
    while time.perf_counter() < deadline:
        with open(log_path, "rb") as fh:
            for line in fh:
                if line.startswith(b'{"crypto_service"') \
                        and line.endswith(b"\n"):
                    return proc, json.loads(line)
        if proc.poll() is not None:
            raise RuntimeError("crypto service died during startup: "
                               + tail())
        time.sleep(0.2)
    stop_processes([proc])
    raise RuntimeError("crypto service never bound its socket: " + tail())


def run_tcp_pool(n_nodes: int = 4, n_txns: int = 200, backend: str = "cpu",
                 base_dir: str | None = None, timeout: float = 120.0,
                 profile_dir: str | None = None,
                 service_min_batch: int | None = None,
                 window: int = 100,
                 config_overrides: dict | None = None,
                 stages: list | None = None) -> dict:
    """stages: optional lists of pre-signed Requests (signers seeded into
    a Wallet whose trustee is TRUSTEE_SEED), each driven to completion
    before the next starts — e.g. NYMs creating DIDs, then writes signed
    by those DIDs. Default: one stage of n_txns trustee-signed NYMs."""
    from plenum_tpu.client.wallet import Wallet
    from plenum_tpu.execution.txn import NYM

    names = [f"Node{i + 1}" for i in range(n_nodes)]
    f = (n_nodes - 1) // 3
    tmp = base_dir or tempfile.mkdtemp(prefix="plenum_tcp_pool_")
    specs = setup_pool_dir(tmp, names, TRUSTEE_SEED)

    # ONE PROCESS PER CHIP. This launcher never initialises a JAX backend
    # (importing the package does not; only a device query or a dispatch
    # does, and it makes neither), and every node process is pinned to
    # JAX_PLATFORMS=cpu: only a "service:jax*" crypto-plane child has the
    # variable removed and so owns the accelerator. A second process
    # touching the chip would fail or hang there.
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    # reproducibility: node config comes ONLY from the explicit param —
    # a stray PLENUM_CONFIG_JSON in the operator shell must not silently
    # reconfigure every bench node
    env.pop("PLENUM_CONFIG_JSON", None)
    if config_overrides:
        env["PLENUM_CONFIG_JSON"] = json.dumps(config_overrides)
    procs = []
    service_proc = None
    service: dict | None = None
    # "service:<inner>" runs the cross-process crypto plane: ONE process
    # owns the device/verifier, nodes ship batches to it (the topology a
    # single TPU chip requires — a chip belongs to one process at a time;
    # parallel/crypto_service.py)
    try:
        if backend.startswith("service:"):
            inner = backend.split(":", 1)[1]
            on_device = inner.startswith("jax")
            sock_path = os.path.join(tmp, "crypto.sock")
            service_env = dict(env)
            if on_device:
                service_env.pop("JAX_PLATFORMS", None)
            # ONE large bucket covers every wave a window can coalesce;
            # min_batch only pads, it never waits. The service holds the
            # 64-lane program beside it and runs short waves there (a
            # 512-lane execution is ~10.6 ms on a v5e, a 64-lane one 4.7)
            min_batch = service_min_batch or (512 if on_device else 128)
            t_setup = time.perf_counter()
            service_proc, started = _start_crypto_service(
                inner, sock_path, min_batch, service_env)
            service = {"device": started.get("device")}
            if on_device:
                # warm BEFORE traffic: every shape a window <= min_batch
                # can coalesce into (one row bucket x both key tables; the
                # service adds its small program to the same prewarm). A
                # compile landing inside a run was measured at 2.7 TPS /
                # p99 97 s; a warm wave the device did not answer raises.
                from plenum_tpu.parallel.crypto_service import \
                    FederatedEd25519Client
                ctl = FederatedEd25519Client(socket_path=sock_path)
                try:
                    ctl.prewarm([min_batch], full_keys=True)
                    ctl.pin()
                    # the owner's stats() as traffic starts: subtract from
                    # result["crypto_service"] for the window alone
                    service["at_pin"] = ctl.stats()
                finally:
                    ctl.close()
            service["setup_s"] = round(time.perf_counter() - t_setup, 3)
            env = dict(env, PLENUM_CRYPTO_SOCKET=sock_path)
            backend = "service"
        for name in names:
            cmd = [sys.executable, "-m", "plenum_tpu.tools.start_node",
                   "--name", name, "--base-dir", tmp, "--kv", "memory",
                   "--backend", backend]
            if profile_dir:
                cmd += ["--profile",
                        os.path.join(profile_dir, f"{name}.pstats")]
            procs.append(_spawn(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT))
        _wait_all_started(procs, deadline_s=60.0)

        if stages is None:
            wallet = Wallet("bench")
            trustee_did = wallet.add_identifier(seed=TRUSTEE_SEED)
            stages = [[]]
            for i in range(n_txns):
                user = wallet.add_identifier(
                    seed=(b"tcpu%d" % i).ljust(32, b"\0")[:32])
                stages[0].append(wallet.sign_request(
                    {"type": NYM, "dest": user,
                     "verkey": wallet.verkey_of(user)},
                    identifier=trustee_did))
        n_txns = sum(len(st) for st in stages)

        addrs = {name: ("127.0.0.1", spec[3])
                 for name, spec in zip(names, specs)}
        t0 = time.perf_counter()
        done, submit_times = {}, {}
        for requests in stages:
            d, st = asyncio.run(drive_load(addrs, f, requests, window=window,
                                           timeout=timeout))
            done.update(d)
            submit_times.update(st)
            if len(d) < len(requests):
                break               # a later stage depends on this one
        t_total = (max(done.values()) - t0) if done else 0.0
        lat = sorted(done[k] - submit_times[k] for k in done)
        service_stats = None
        if service_proc is not None:
            try:
                from plenum_tpu.parallel.crypto_service import \
                    ServiceEd25519Verifier
                service_stats = ServiceEd25519Verifier(
                    socket_path=env["PLENUM_CRYPTO_SOCKET"]).stats()
            except Exception:
                pass
        result = {
            **({"crypto_service": service_stats} if service_stats else {}),
            **({"service": service} if service is not None else {}),
            "transport": "tcp", "nodes": n_nodes, "backend": backend,
            "txns_ordered": len(done), "txns_requested": n_txns,
            "seconds": round(t_total, 3),
            "tps": round(len(done) / t_total, 1) if t_total > 0 else 0.0,
            "p50_latency_ms": round(
                statistics.median(lat) * 1000, 1) if lat else None,
            "p99_latency_ms": round(
                lat[int(len(lat) * 0.99)] * 1000, 1) if lat else None,
        }
        # bytes-on-wire + loss accounting from a node's flushed metrics
        # history (SIGTERM first so the tail flush carries final totals)
        stop_processes(procs)
        try:
            from plenum_tpu.tools.metrics_report import (derive_summary,
                                                         fold_rows,
                                                         read_store)
            folds = fold_rows(read_store(os.path.join(tmp, names[0],
                                                      "metrics")))
            # one derivation (cum-as-max, propagate op set) lives in
            # metrics_report; this just renames the keys the bench wants
            summary = derive_summary(folds, 0.0)
            for src, dst in (
                    ("transport_tx_bytes_per_txn", "tx_bytes_per_txn"),
                    ("propagate_tx_bytes_per_txn",
                     "propagate_tx_bytes_per_txn"),
                    ("transport_dropped_frames", "dropped_frames"),
                    ("propagate_inbox_depth_max",
                     "propagate_inbox_depth_max")):
                if summary.get(src) is not None:
                    result[dst] = summary[src]
            # commit-path stage percentiles + pairing/group-commit counters
            # (derive_summary computes them from the flushed raw samples)
            stage = {k: summary[k] for k in summary
                     if k.startswith(("bls_verify_ms", "apply_ms",
                                      "durable_ms", "reply_ms"))
                     or k in ("pairings_per_batch",
                              "group_commit_batches_mean",
                              "plane_dispatches")}
            if stage:
                result["commit_stage"] = stage
            # plane-supervisor health: breaker state, fallback volume,
            # hedge wins, deadline distribution (degraded-mode acceptance:
            # these must be on the bench line, not buried in a KV store).
            # Gated on the breaker gauge: only configs that actually RAN a
            # device plane report a backend_state — a pure-CPU pool must
            # not claim a healthy device it never had.
            if "crypto_breaker_state" in summary:
                plane = {k: summary[k] for k in summary
                         if k.startswith(("crypto_", "deadline_ms_",
                                          "bls_batch", "bls_local"))}
                result["crypto_plane"] = plane
                result["backend_state"] = {
                    "closed": "ok", "half_open": "fallback",
                    "open": "open"}.get(
                        plane["crypto_breaker_state"], "ok")
        except Exception:
            pass                     # byte accounting is best-effort extra
        return result
    finally:
        stop_processes(procs + [service_proc])
        if base_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--txns", type=int, default=200)
    ap.add_argument("--backend", default="cpu",
                    choices=["cpu", "jax", "service:cpu", "service:jax",
                             "service:jax-sharded"])
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    stats = run_tcp_pool(args.nodes, args.txns, args.backend)
    if args.json:
        print(json.dumps(stats))
    else:
        print(f"{stats['txns_ordered']}/{stats['txns_requested']} txns in "
              f"{stats['seconds']}s over TCP -> {stats['tps']} TPS "
              f"(p50 {stats['p50_latency_ms']} ms, "
              f"p99 {stats['p99_latency_ms']} ms)")


if __name__ == "__main__":
    main()
