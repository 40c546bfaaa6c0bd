"""The remaining BASELINE.json benchmark configs (2-5).

BASELINE.json names five configs; tools/local_pool + tools/tcp_pool cover
config 1 (4-node NYM writes). This module measures the rest, each as one
function returning a small stats dict that bench.py folds into its extras:

  config2  4-node pool, THREE RBFT protocol instances, mixed NYM/ATTRIB
  config3  BLS state-proof reads: GET_NYM queries answered with a state
           proof + BLS multi-signature (single node serves reads)
  config4  7-node / f=2 pool over real TCP, view change UNDER LOAD (the
           master primary process is killed mid-drive)
  config5  25-node simulated pool ordering datum

Every function is wall-clock bounded and returns {"error": ...} instead of
raising — bench.py must always print its one JSON line.
"""
from __future__ import annotations

import json
import time


def _mixed_requests(trustee, n: int):
    """NYM-create for even i, ATTRIB for odd i. ATTRIBs are trustee-
    authored (a trustee may set attributes on any DID) and target a DID
    created >=128 requests earlier — or the genesis trustee itself — so
    an in-flight window never races a dest's NYM commit: a fresh DID is
    unusable until its NYM lands, exactly as for real clients."""
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import ATTRIB, NYM

    users = []
    reqs = []
    for i in range(n):
        if i % 2 == 0:
            user = Ed25519Signer(seed=(b"mix%08d" % i).ljust(32, b"\0")[:32])
            users.append(user)
            req = Request(trustee.identifier, i + 1,
                          {"type": NYM, "dest": user.identifier,
                           "verkey": user.verkey_b58})
        else:
            settled = len(users) - 64          # 64 NYMs = 128 requests ago
            dest = users[(i // 2) % settled].identifier if settled > 0 \
                else trustee.identifier
            req = Request(trustee.identifier, i + 1,
                          {"type": ATTRIB, "dest": dest,
                           "raw": json.dumps({"endpoint%d" % i: str(i)})})
        req.signature = trustee.sign_b58(req.signing_bytes())
        reqs.append(req)
    return reqs


def _drive_inprocess(names, nodes, timer, replies, Reply, plane, requests,
                     timeout: float):
    t0 = time.perf_counter()
    done: set = set()
    i = 0
    while len(done) < len(requests) and time.perf_counter() < t0 + timeout:
        while i < len(requests) and i - len(done) < 256:
            for n in names:
                nodes[n].handle_client_message(requests[i].to_dict(), "bench")
            i += 1
        timer.service()
        for node in nodes.values():
            node.prod()
        if plane is not None:
            plane.flush()
        for _, msg, _c in replies[names[0]]:
            if isinstance(msg, Reply):
                d = msg.result.get("txn", {}).get("metadata", {}).get("digest")
                if d:
                    done.add(d)
        replies[names[0]].clear()
    return len(done), time.perf_counter() - t0


def config2_three_instances_mixed(n_txns: int = 200,
                                  timeout: float = 120.0) -> dict:
    """4 nodes, 3 RBFT instances, mixed NYM/ATTRIB writes."""
    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.common.node_messages import Reply
    from plenum_tpu.common.timer import QueueTimer
    from plenum_tpu.config import Config
    from plenum_tpu.network import SimNetwork, SimRandom
    from plenum_tpu.node import Node, NodeBootstrap

    try:
        names = [f"Node{i + 1}" for i in range(4)]
        genesis, trustee = lp.build_genesis(names)
        timer = QueueTimer(time.perf_counter)
        net = SimNetwork(timer, SimRandom(7))
        net.set_latency(0.00005, 0.0002)
        config = Config(Max3PCBatchWait=0.05,
                        STATE_FRESHNESS_UPDATE_INTERVAL=600.0)
        replies = {n: [] for n in names}
        nodes = {}
        for name in names:
            bus = net.create_peer(name)
            comp = NodeBootstrap(name, genesis_txns=genesis).build()
            nodes[name] = Node(
                name, timer, bus, comp,
                client_send=lambda msg, client, n=name: replies[n].append(
                    (time.perf_counter(), msg, client)),
                config=config, instance_count=3)
        net.connect_all()
        assert all(len(nd.replicas) == 3 for nd in nodes.values())

        reqs = _mixed_requests(trustee, n_txns)
        done, dt = _drive_inprocess(names, nodes, timer, replies, Reply,
                                    None, reqs, timeout)
        # backups shadow-order slightly behind the master's replies; give
        # them a drain window before reading their progress gauge
        for _ in range(400):
            timer.service()
            for node in nodes.values():
                node.prod()
        # every backup instance must be shadow-ordering, or the "3
        # instances" claim is hollow
        inst_progress = [
            min(nodes[n].replicas[i].data.last_ordered_3pc[1]
                for n in names) for i in (0, 1, 2)]
        return {"txns_ordered": done, "txns_requested": n_txns,
                "tps": round(done / dt, 1) if dt else 0.0,
                "instances": 3,
                "min_backup_ordered": min(inst_progress[1:]),
                }
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def config3_bls_proof_reads(n_reads: int = 2000,
                            timeout: float = 120.0) -> dict:
    """GET_NYM state-proof read throughput on one node, with the BLS
    multi-signature attached (ref docs/source/main.md:24 — one node's
    reply suffices because the proof + multi-sig carry the trust)."""
    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.common.node_messages import Reply
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import GET_NYM, NYM

    try:
        (names, nodes, timer, trustee,
         replies, ReplyCls, DOMAIN, plane, net) = lp.build_pool(4, "cpu")
        # commit a handful of NYMs so the BLS store holds multi-sigs
        users = []
        reqs = []
        for i in range(20):
            user = Ed25519Signer(seed=(b"rd%08d" % i).ljust(32, b"\0")[:32])
            users.append(user)
            req = Request(trustee.identifier, i + 1,
                          {"type": NYM, "dest": user.identifier,
                           "verkey": user.verkey_b58})
            req.signature = trustee.sign_b58(req.signing_bytes())
            reqs.append(req)
        done, _ = _drive_inprocess(names, nodes, timer, replies, ReplyCls,
                                   plane, reqs, 60.0)
        if done < len(reqs):
            return {"error": f"setup ordered only {done}/{len(reqs)}"}

        node = nodes[names[0]]
        served = 0
        with_multisig = 0
        t0 = time.perf_counter()
        i = 0
        while served < n_reads and time.perf_counter() < t0 + timeout:
            q = Request("reader", i + 1,
                        {"type": GET_NYM,
                         "dest": users[i % len(users)].identifier})
            node.handle_client_message(q.to_dict(), "reader")
            i += 1
            if i % 100 == 0 or i >= n_reads:
                node.prod()
                for _, msg, _c in replies[names[0]]:
                    if isinstance(msg, ReplyCls) and \
                            msg.result.get("type") == GET_NYM:
                        served += 1
                        if msg.result.get("state_proof", {}) \
                                .get("multi_signature"):
                            with_multisig += 1
                replies[names[0]].clear()
        dt = time.perf_counter() - t0
        return {"reads_served": served,
                "reads_with_multisig": with_multisig,
                "reads_per_s": round(served / dt, 1) if dt else 0.0}
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def config4_viewchange_under_load(n_txns: int = 150,
                                  timeout: float = 150.0) -> dict:
    """7-node / f=2 TCP pool; the master primary's OS process is SIGKILLed
    mid-drive. Done = the remaining requests still finish (view change
    under load) and the figure reports effective TPS across the fault."""
    import asyncio
    import os
    import shutil
    import signal
    import subprocess
    import sys
    import tempfile

    from plenum_tpu.tools.tcp_pool import (REPO, _wait_all_started,
                                           setup_pool_dir)

    names = [f"Node{i + 1}" for i in range(7)]
    tmp = tempfile.mkdtemp(prefix="plenum_vc_pool_")
    trustee_seed = b"vc-pool-trustee!".ljust(32, b"\0")
    procs = []
    try:
        specs = setup_pool_dir(tmp, names, trustee_seed)
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        for name in names:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "plenum_tpu.tools.start_node",
                 "--name", name, "--base-dir", tmp, "--kv", "memory"],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT))
        _wait_all_started(procs, deadline_s=90.0)

        from plenum_tpu.client.wallet import Wallet
        from plenum_tpu.execution.txn import NYM
        wallet = Wallet("vc-bench")
        trustee = wallet.add_identifier(seed=trustee_seed)
        requests = []
        for i in range(n_txns):
            user = wallet.add_identifier(
                seed=(b"vcu%05d" % i).ljust(32, b"\0")[:32])
            requests.append(wallet.sign_request(
                {"type": NYM, "dest": user,
                 "verkey": wallet.verkey_of(user)}, identifier=trustee))
        addrs = {name: ("127.0.0.1", spec[3])
                 for name, spec in zip(names, specs)}

        async def drive():
            from plenum_tpu.client.pipelined import PipelinedPoolClient
            client = PipelinedPoolClient(addrs, f=2)

            async def killer():
                await asyncio.sleep(1.0)         # mid-load
                procs[0].send_signal(signal.SIGKILL)   # Node1 = primary

            kill_task = asyncio.create_task(killer())
            # window matches the headline TCP-pool config (bench.py
            # window=250) so "TPS across the fault" is comparable to the
            # steady-state 7-node figure from the same bench run
            done, submit = await client.drive(requests, window=250,
                                              timeout=timeout)
            await kill_task
            return done, submit

        t0 = time.perf_counter()
        done, _submit = asyncio.run(drive())
        dt = time.perf_counter() - t0
        out = {"txns_ordered": len(done), "txns_requested": n_txns,
               "primary_killed_at_s": 1.0,
               "recovered": len(done) == n_txns,
               "tps_across_fault": round(len(done) / dt, 1) if dt else 0.0}
        # the fault's cost, separated from run length: the stall is the
        # longest gap between consecutive request completions, and the
        # steady rate is what the pool does outside that gap
        times = sorted(done.values())
        if len(times) > 2:
            gaps = [b - a for a, b in zip(times, times[1:])]
            stall = max(gaps)
            out["stall_s"] = round(stall, 2)
            span = times[-1] - times[0] - stall
            if span > 0:
                out["steady_tps_outside_stall"] = round(
                    (len(times) - 2) / span, 1)
        # per-phase stall decomposition from a SURVIVOR's flushed metrics
        # store (nodes were just SIGTERMed -> tail flush ran)
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        try:
            from plenum_tpu.tools.metrics_report import (fold_rows,
                                                         read_store)
            folds = fold_rows(read_store(
                os.path.join(tmp, names[1], "metrics")))
            for short, metric in (
                    ("detect_to_vote", "consensus.vc_detect_to_vote"),
                    ("vote_to_start", "consensus.vc_vote_to_start"),
                    ("start_to_new_view",
                     "consensus.vc_start_to_new_view"),
                    ("new_view_to_order",
                     "consensus.vc_new_view_to_order")):
                f = folds.get(metric)
                if f and f.get("count"):
                    out[f"vc_{short}_s"] = round(f["sum"] / f["count"], 3)
        except Exception:
            pass                     # decomposition is best-effort extra
        return out
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def _host_calib_ms() -> float:
    """Fixed deterministic CPU spin, timed. The sim25 figure is a pure
    single-process CPU measurement, so host contention scales it directly
    — the BENCH_r04/r05 'regression' (47-52 -> 13-15 TPS) reproduced at
    ~45-53 TPS on an idle host with the very same code, while the bench
    rounds ran it last in a round that had just hammered the host with
    multi-process TCP pools. This calibration figure rides the bench line
    so a contended round is READABLE as contended (calib_ms inflates with
    the same factor) instead of masquerading as an ordering regression."""
    import hashlib
    t0 = time.perf_counter()
    block = b"\0" * 65536
    h = hashlib.sha256()
    for _ in range(200):
        h.update(block)
    return round((time.perf_counter() - t0) * 1000, 2)


def _sim25_once(n_txns: int, timeout: float, config_overrides=None,
                topology: str = None) -> dict:
    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM

    (names, nodes, timer, trustee,
     replies, ReplyCls, DOMAIN, plane, net) = lp.build_pool(
         25, "cpu", config_overrides=config_overrides)
    if topology is not None:
        from plenum_tpu.network import make_topology
        net.set_topology(make_topology(topology, names))
    reqs = []
    for i in range(n_txns):
        user = Ed25519Signer(seed=(b"s25_%05d" % i).ljust(32, b"\0")[:32])
        req = Request(trustee.identifier, i + 1,
                      {"type": NYM, "dest": user.identifier,
                       "verkey": user.verkey_b58})
        req.signature = trustee.sign_b58(req.signing_bytes())
        reqs.append(req)
    done, dt = _drive_inprocess(names, nodes, timer, replies, ReplyCls,
                                plane, reqs, timeout)
    wire = net.bytes_summary()
    prop = sum(c["bytes"] for op, c in wire["by_type"].items()
               if op in ("PROPAGATE", "PROPAGATE_BATCH"))
    stage = lp.commit_stage_stats(nodes[names[0]].metrics)
    ctl = getattr(nodes[names[0]], "batch_controller", None)
    return {"nodes": 25, "txns_ordered": done, "txns_requested": n_txns,
            "tps": round(done / dt, 1) if dt else 0.0,
            "wire_bytes_per_txn": round(wire["total_bytes"] / done)
            if done else None,
            "propagate_bytes_per_txn": round(prop / done)
            if done else None,
            **({"controller": ctl.trajectory()} if ctl is not None else {}),
            **({"commit_stage": stage} if stage else {})}


def config5_sim25(n_txns: int = 60, timeout: float = 180.0) -> dict:
    """25-node simulated pool (SimNetwork fabric, one process) ordering
    datum — the scale test's shape (tests/test_scale.py) with a number.

    Runs an A/B: the default deep-pipelined + controller-steered ordering
    vs the legacy static knobs (in-flight window 4, no controller), plus a
    host-contention calibration so a loaded bench host can't masquerade as
    an ordering regression (see _host_calib_ms). Tracing note: this config
    runs the NullTracer fast path — it keeps NO tracing overhead, and the
    calib figure is the only non-pool work it pays for."""
    try:
        calib = _host_calib_ms()
        # One DISCARDED warm-up pass, then 3 runs per arm INTERLEAVED and
        # medians taken: single sim25 passes ride a ±20% host-noise band
        # (the r04/r05 lesson), and the first pool in a process runs
        # measurably cold — an A/B that always ran one arm first
        # systematically penalized it (measured: same arm 54.7 first vs
        # 67.6 fourth in one process).
        legacy_cfg = {"BATCH_CONTROLLER": False, "Max3PCBatchesInFlight": 4}
        _sim25_once(n_txns, timeout)             # warm-up, discarded
        runs, legacy_runs = [], []
        for _ in range(3):
            runs.append(_sim25_once(n_txns, timeout))
            legacy_runs.append(_sim25_once(n_txns, timeout,
                                           config_overrides=legacy_cfg))
        runs.sort(key=lambda r: r["tps"])
        legacy_runs.sort(key=lambda r: r["tps"])
        out = runs[1]
        out["tps_spread"] = {"min": runs[0]["tps"], "max": runs[-1]["tps"]}
        out["calib_ms"] = calib
        out["tracing_overhead"] = "none (NullTracer fast path)"
        out["legacy_tps"] = legacy_runs[1].get("tps")
        return out
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def config9_wan25(n_txns: int = 40, timeout: float = 240.0) -> dict:
    """25-node pool over the TOPOLOGY-AWARE fabric: the same sim25 shape,
    once per region preset (geo3 clean WAN, lossy_wan degraded). The
    orderings-still-happen number the WAN robustness work is judged by —
    and the honest cost of geography: the delta vs config5's flat-LAN
    figure is propagation+loss, not code. Real time (QueueTimer), so WAN
    delays are actually waited out; txn count kept small accordingly."""
    out: dict = {"nodes": 25, "txns_requested": n_txns}
    try:
        for preset in ("geo3", "lossy_wan"):
            run = _sim25_once(n_txns, timeout, topology=preset)
            out[preset] = {k: run.get(k) for k in
                           ("txns_ordered", "tps", "wire_bytes_per_txn")}
        return out
    except Exception as e:                       # pragma: no cover
        out["error"] = f"{type(e).__name__}: {e}"
        return out





def config6_read_plane(n_reads: int = 1800, write_every: int = 9,
                       timeout: float = 120.0) -> dict:
    """Read-heavy mix (90:10 read:write) through the VERIFIED read plane:
    every read goes to ONE node and the client checks the state proof +
    BLS multi-sig + freshness (reads/client.py). Reports reads/s, the
    measured per-read fanout (messages per read, target 2 = 1 request +
    1 reply vs the legacy 2n broadcast), client verify p50/p95, and the
    serving node's cache hit rate."""
    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import GET_NYM, NYM
    from plenum_tpu.reads import SimReadDriver

    try:
        (names, nodes, timer, trustee,
         replies, ReplyCls, DOMAIN, plane, net) = lp.build_pool(4, "cpu")
        users = []
        setup = []
        for i in range(20):
            user = Ed25519Signer(seed=(b"rp%08d" % i).ljust(32, b"\0")[:32])
            users.append(user)
            req = Request(trustee.identifier, i + 1,
                          {"type": NYM, "dest": user.identifier,
                           "verkey": user.verkey_b58})
            req.signature = trustee.sign_b58(req.signing_bytes())
            setup.append(req)
        done, _ = _drive_inprocess(names, nodes, timer, replies, ReplyCls,
                                   plane, setup, 60.0)
        if done < len(setup):
            return {"error": f"setup ordered only {done}/{len(setup)}"}

        bls_keys = lp.pool_bls_keys(names)

        def submit(name, req):
            nodes[name].handle_client_message(req.to_dict(), "rdr")

        def collect(name):
            out = [m.result for _, m, c in replies[name]
                   if isinstance(m, ReplyCls) and c == "rdr"]
            replies[name].clear()
            return out

        def pump(seconds):
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                timer.service()
                for node in nodes.values():
                    node.prod()

        driver = SimReadDriver(submit, collect, pump, names, bls_keys,
                               freshness_s=1e9,
                               now=timer.get_current_time)
        served = 0
        writes = 0
        write_id = 1000
        t0 = time.perf_counter()
        for i in range(n_reads):
            if time.perf_counter() > t0 + timeout:
                break
            if i % write_every == write_every - 1:
                # the write share of the 90:10 mix, fire-and-forget
                user = Ed25519Signer(
                    seed=(b"rpw%07d" % i).ljust(32, b"\0")[:32])
                w = Request(trustee.identifier, write_id,
                            {"type": NYM, "dest": user.identifier,
                             "verkey": user.verkey_b58})
                w.signature = trustee.sign_b58(w.signing_bytes())
                write_id += 1
                for n in names:
                    nodes[n].handle_client_message(w.to_dict(), "bench-w")
                writes += 1
            q = Request("reader", i + 1,
                        {"type": GET_NYM,
                         "dest": users[i % len(users)].identifier})
            if driver.read(q, per_node_s=2.0, step_s=0.001) is not None:
                served += 1
        dt = time.perf_counter() - t0
        s = driver.stats.summary()
        rp = nodes[names[0]].read_plane.stats
        out = {"reads_served": served, "writes_submitted": writes,
               "reads_per_s": round(served / dt, 1) if dt else 0.0,
               "read_fanout": s.get("fanout"),
               "legacy_read_fanout": 2 * len(names),
               "single_reply_ok": s["single_reply_ok"],
               "failovers": s["failovers"], "fallbacks": s["fallbacks"],
               "verify_ms_p50": s.get("verify_ms_p50"),
               "verify_ms_p95": s.get("verify_ms_p95")}
        if rp["queries"]:
            out["server_cache_hit_rate"] = round(
                rp["cache_hits"] / rp["queries"], 3)
        return out
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def config7_ingress_10k(n_clients: int = 10_000, n_ops: int = 3000,
                        burst_clients: int = 200, burst_per_client: int = 10,
                        timeout: float = 180.0) -> dict:
    """10k-simulated-client, 95:5 read:write mix through the whole
    ingress plane (docs/ingress.md):

      * writes enter each node through an IngressPlane — admission
        control, weighted-fair dequeue, and ONE batched Ed25519 dispatch
        per tick through the ReqAuthenticator seam (the published
        auth_batch_mean must be >> 1 for the amortization claim);
      * reads are served by TWO observers replicating via BatchCommitted
        pushes (multi-sig verified before anchoring) with client-side
        proof verification (SimReadDriver, observer tier first);
      * an overload A/B floods one front door: the ingress arm holds
        queue depth at the watermark with explicit LoadShed replies and
        the pool KEEPS ordering (zero wedges), while the no-ingress arm
        swallows the whole burst into the node inbox unboundedly.
    """
    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.client.sim_clients import (SimClientPopulation,
                                               burst_writes)
    from plenum_tpu.common.node_messages import BatchCommitted
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM
    from plenum_tpu.ingress import IngressPlane, SimObserver
    from plenum_tpu.reads import SimReadDriver

    try:
        (names, nodes, timer, trustee,
         replies, ReplyCls, DOMAIN, plane, net) = lp.build_pool(4, "cpu")
        bls_keys = lp.pool_bls_keys(names)

        # observers BEFORE traffic: pushes only cover live batches.
        # build_genesis is deterministic per name set, so the observers
        # bootstrap from byte-identical genesis txns
        genesis, _ = lp.build_genesis(names)
        observers = {
            f"obs{i + 1}": SimObserver(
                f"obs{i + 1}", genesis, names, bls_keys,
                now=timer.get_current_time, f=1, anchor_lag_max=None)
            for i in range(2)}
        for obs in observers.values():
            obs.register(lambda v, msg, o=obs: nodes[v]
                         .handle_client_message(msg, o.client_id))

        ingress = {n: IngressPlane(nodes[n], tick=False) for n in names}

        def route_pushes():
            """Move BatchCommitted pushes out of the validator client
            outboxes into the observers."""
            for v in names:
                keep = []
                for ts, msg, client in replies[v]:
                    obs = observers.get(
                        client[4:] if client.startswith("obs:") else "")
                    if obs is not None and isinstance(msg, BatchCommitted):
                        obs.deliver_push(msg, v)
                    else:
                        keep.append((ts, msg, client))
                replies[v][:] = keep

        def step():
            timer.service()
            for node in nodes.values():
                node.prod()
            for ing in ingress.values():
                ing.service()
            route_pushes()

        # setup: 20 read-target DIDs ordered through the INGRESS plane
        users = []
        t0 = time.perf_counter()
        for i in range(20):
            user = Ed25519Signer(seed=(b"i7%08d" % i).ljust(32, b"\0")[:32])
            users.append(user)
            req = Request(trustee.identifier, i + 1,
                          {"type": NYM, "dest": user.identifier,
                           "verkey": user.verkey_b58})
            req.signature = trustee.sign_b58(req.signing_bytes())
            for n in names:
                ingress[n].submit(req.to_dict(), "setup")
        domain = nodes[names[0]].c.db.get_ledger(DOMAIN)
        while domain.size < 21 and time.perf_counter() < t0 + 60.0:
            step()
        if domain.size < 21:
            return {"error": f"setup ordered only {domain.size - 1}/20"}
        base_size = domain.size

        # --- the 95:5 mixed drive ------------------------------------
        def submit(name, req):
            if name in observers:
                observers[name].handle_client_message(req.to_dict(), "rdr")
            else:
                nodes[name].handle_client_message(req.to_dict(), "rdr")

        def collect(name):
            if name in observers:
                out = [m.result for m, c in observers[name].sent
                       if isinstance(m, ReplyCls)]
                observers[name].sent.clear()
                return out
            out = [m for _, m, c in replies[name]
                   if isinstance(m, ReplyCls) and c == "rdr"]
            replies[name][:] = [e for e in replies[name]
                                if not (isinstance(e[1], ReplyCls)
                                        and e[2] == "rdr")]
            return [m.result for m in out]

        def pump(seconds):
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                step()

        driver = SimReadDriver(submit, collect, pump, names, bls_keys,
                               freshness_s=1e9,
                               now=timer.get_current_time,
                               observer_names=sorted(observers))
        pop = SimClientPopulation(n_clients, trustee,
                                  [u.identifier for u in users], seed=7)
        served = writes = 0
        t0 = time.perf_counter()
        # wave-shaped drive: each wave's writes land in the ingress
        # queues FIRST and ride the tick's ONE auth dispatch together
        # (real front doors see concurrent arrivals, not one write per
        # service tick); the wave's reads then run against the observers
        ops = list(pop.ops(n_ops))
        wave_size = 100
        for w0 in range(0, len(ops), wave_size):
            if time.perf_counter() > t0 + timeout:
                break
            wave = ops[w0:w0 + wave_size]
            for client_id, kind, req in wave:
                if kind == "write":
                    for n in names:
                        ingress[n].submit(req.to_dict(), client_id)
                    writes += 1
            step()
            for client_id, kind, req in wave:
                if kind == "read":
                    if driver.read(req, per_node_s=2.0,
                                   step_s=0.001) is not None:
                        served += 1
        # drain the tail of in-flight writes
        t_drain = time.perf_counter() + 20.0
        while (domain.size - base_size) < writes and \
                time.perf_counter() < t_drain:
            step()
        dt = time.perf_counter() - t0
        # SNAPSHOT before the overload arms order their own flood writes
        writes_ordered = domain.size - base_size
        s = driver.stats.summary()
        ing_sum = ingress[names[0]].summary()

        # --- overload A/B --------------------------------------------
        # arm A: flood ONE ingress front door; queue depth stays at the
        # watermark, the surplus sheds explicitly, the pool keeps
        # ordering. Watermarks scale with the burst (a quarter of it) so
        # the A/B sheds decisively at any parameterization and still
        # drains in seconds.
        burst = burst_writes(trustee, burst_clients, burst_per_client,
                             seed=7)
        wm = max(32, len(burst) // 4)
        flood_cfg = nodes[names[0]].config.replace(
            INGRESS_HIGH_WATERMARK=wm,
            INGRESS_LOW_WATERMARK=max(8, wm // 4),
            INGRESS_CLIENT_QUEUE_CAP=max(2, burst_per_client // 2),
            INGRESS_CONTROLLER=False)
        flood_ing = IngressPlane(nodes[names[0]], config=flood_cfg,
                                 tick=False)
        size_before = domain.size
        for client, req in burst:
            flood_ing.submit(req.to_dict(), client)

        def flood_step():
            step()
            flood_ing.service()          # tick=False: serviced here

        t_flood = time.perf_counter() + 15.0
        while time.perf_counter() < t_flood and flood_ing.queue_depth:
            flood_step()
        # the queue drains into dispatches before ordering completes:
        # give the pool a bounded window to show it KEPT ordering the
        # admitted subset (the zero-wedge claim), not just shedding
        admitted = flood_ing.stats["admitted"]
        t_flood = time.perf_counter() + 20.0
        while domain.size - size_before < admitted and \
                time.perf_counter() < t_flood:
            flood_step()
        fa = flood_ing.summary()
        arm_a = {
            "burst": len(burst),
            "watermark": wm,
            "queue_depth_peak": fa["queue_depth_max"],
            "bounded": fa["queue_depth_max"] <= wm,
            "shed": fa["shed"],
            "admitted": admitted,
            "auth_batch_mean": fa.get("auth_batch_mean"),
            "ordered_after_flood": domain.size - size_before,
            "inbox_peak": max((len(nodes[n]._client_inbox)
                               for n in names), default=0),
        }
        # arm B: the same burst straight into the node inbox — nothing
        # sheds, the inbox swallows the whole flood (unbounded growth)
        for client, req in burst:
            nodes[names[0]].handle_client_message(req.to_dict(), client)
        arm_b = {"burst": len(burst),
                 "inbox_depth_after_burst":
                     len(nodes[names[0]]._client_inbox)}
        t_flood = time.perf_counter() + 30.0
        while nodes[names[0]]._client_inbox and \
                time.perf_counter() < t_flood:
            step()

        return {
            "clients": n_clients, "ops": n_ops,
            "reads_served": served, "writes_submitted": writes,
            "writes_ordered": writes_ordered,
            "reads_per_s": round(served / dt, 1) if dt else 0.0,
            "observer_served": s.get("observer_ok", 0),
            "read_fanout": s.get("fanout"),
            "verify_ms_p50": s.get("verify_ms_p50"),
            "verify_ms_p95": s.get("verify_ms_p95"),
            "auth_batch_mean": ing_sum.get("auth_batch_mean"),
            "auth_batches": ing_sum.get("auth_batches"),
            "ingress_admitted": ing_sum.get("admitted"),
            "ingress_shed": ing_sum.get("shed"),
            **({"ingress_controller": ing_sum["controller"]}
               if "controller" in ing_sum else {}),
            "overload_ab": {"ingress": arm_a, "no_ingress": arm_b},
        }
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def _sharded_arm_once(n_shards: int, nodes_per_shard: int, n_txns: int,
                      timeout: float, n_reads: int = 60,
                      cross_fraction: float = 0.5) -> dict:
    """One real-time pass over a ShardedSimFabric: route `n_txns` writes
    across the shards, then run a read mix where `cross_fraction` of the
    reads target keys owned by a NON-home shard (home = shard 0, the
    reader's local one) — every read composes mapping-ownership +
    shard-anchor verification either way; the fraction only steers which
    shard answers. n_shards=1 IS the matched-node-count baseline: the
    identical code path (router, gates, composed verification) over one
    ordering instance, so the A/B isolates the sharding, not the plumbing."""
    import time as _time

    from plenum_tpu.common.request import Request
    from plenum_tpu.common.timer import QueueTimer
    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import GET_NYM, NYM
    from plenum_tpu.shards import ShardedSimFabric

    fab = ShardedSimFabric(
        n_shards=n_shards, nodes_per_shard=nodes_per_shard,
        timer=QueueTimer(_time.perf_counter), seed=11,
        config=Config(Max3PCBatchWait=0.05,
                      STATE_FRESHNESS_UPDATE_INTERVAL=600.0),
        latency=(0.00005, 0.0002))

    users = []
    reqs = []
    for i in range(n_txns):
        user = Ed25519Signer(seed=(b"sh%08d" % i).ljust(32, b"\0")[:32])
        req = Request(fab.trustee.identifier, i + 1,
                      {"type": NYM, "dest": user.identifier,
                       "verkey": user.verkey_b58})
        req.signature = fab.trustee.sign_b58(req.signing_bytes())
        users.append(user)
        reqs.append(req)

    def ordered_total():
        return sum(s.ordered_count() for s in fab.shards.values())

    base = ordered_total()
    t0 = _time.perf_counter()
    i = 0
    while ordered_total() - base < n_txns and \
            _time.perf_counter() < t0 + timeout:
        while i < n_txns and i - (ordered_total() - base) < 256:
            fab.submit_write(reqs[i])
            i += 1
        fab.prod_all()
        if fab.pipeline is not None:
            fab.pipeline.flush()
    dt = _time.perf_counter() - t0
    done = ordered_total() - base
    per_shard = {sid: s.ordered_count() for sid, s in fab.shards.items()}

    # read phase: home-vs-cross mix through the composed verifier
    def pump(seconds):
        t_end = _time.perf_counter() + seconds
        while _time.perf_counter() < t_end:
            fab.prod_all()

    driver = fab.read_driver(pump=pump)
    home, away = [], []
    for u in users:
        req = Request("r", 1, {"type": GET_NYM, "dest": u.identifier})
        (home if fab.router.shard_of(req) == 0 else away).append(u)
    served = cross_served = 0
    t1 = _time.perf_counter()
    for j in range(n_reads):
        cross = (j % 10) < cross_fraction * 10 and away
        pool_u = away if cross else (home or away)
        if not pool_u:
            break
        u = pool_u[j % len(pool_u)]
        q = Request("reader", j + 1, {"type": GET_NYM, "dest": u.identifier})
        if driver.read(q, per_node_s=2.0, step_s=0.001) is not None:
            served += 1
            if cross:
                cross_served += 1
    read_dt = _time.perf_counter() - t1
    s = driver.stats.summary()
    return {
        "shards": n_shards, "nodes": n_shards * nodes_per_shard,
        "txns_ordered": done, "txns_requested": n_txns,
        "seconds": round(dt, 2),
        "aggregate_tps": round(done / dt, 1) if dt else 0.0,
        "per_shard_tps": {str(sid): round(n / dt, 1) if dt else 0.0
                          for sid, n in per_shard.items()},
        "router": fab.router.summary(),
        "reads_served": served, "cross_shard_served": cross_served,
        "reads_per_s": round(served / read_dt, 1) if read_dt else 0.0,
        "cross_verify_ms_p50": s.get("verify_ms_p50"),
        "cross_verify_ms_p95": s.get("verify_ms_p95"),
        "map_proof_failures": s.get("map_proof_failures"),
    }


def config10_shards(n_txns: int = 120, timeout: float = 240.0) -> dict:
    """Horizontal sharding A/B on the bench line (docs/sharding.md): 2-
    and 4-shard fabrics vs the SINGLE-shard pool at MATCHED total node
    count, under a 95:5-shaped load (the write drive + a cross-shard
    read mix at 50% cross fraction). Interleaved medians of 3 after one
    discarded warm-up pass, per the config5/config8 methodology (the
    first pool per process runs cold; host noise rides a ±20% band).

    The acceptance figure is speedup_2x4 = 2-shard aggregate write TPS /
    matched 8-node single-pool TPS (target >= 1.6): the per-txn ordering
    work in a 4-node shard is a fraction of an 8-node pool's (quadratic
    3PC messaging, half the commit sigs), so splitting the SAME total
    node count two ways buys super-linear aggregate throughput."""
    try:
        arms = {
            "single_8": (1, 8),
            "sharded_2x4": (2, 4),
            "sharded_4x2": (4, 2),
        }
        _sharded_arm_once(2, 4, max(20, n_txns // 4), timeout)   # warm-up
        runs: dict[str, list] = {k: [] for k in arms}
        for _ in range(3):
            for k, (ns, npn) in arms.items():        # interleaved
                runs[k].append(_sharded_arm_once(ns, npn, n_txns, timeout))

        def med(rs):
            good = sorted((r for r in rs if r.get("txns_ordered")),
                          key=lambda r: r["aggregate_tps"])
            return good[len(good) // 2] if good else {"error": "no runs"}

        out = {k: med(v) for k, v in runs.items()}
        base = out["single_8"].get("aggregate_tps") or 0.0
        two = out["sharded_2x4"].get("aggregate_tps") or 0.0
        four = out["sharded_4x2"].get("aggregate_tps") or 0.0
        if base:
            out["speedup_2x4"] = round(two / base, 2)
            out["speedup_4x2"] = round(four / base, 2)
        return out
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def _pipeline_ab_inproc(n_txns: int = 150, repeat: int = 3) -> dict:
    """The fused-pipeline A/B, run INSIDE a JAX_PLATFORMS=cpu subprocess
    (config8_pipeline_ab spawns it): the SAME 4-node write load through
    (a) the pipeline ring (cross-stage + cross-node coalescing/dedup) and
    (b) the per-call baseline — every node its own supervised device
    verifier, every call site's batch dispatched alone. WARMED and
    INTERLEAVED per the PR 6 methodology (the first pool per process pays
    the XLA compiles and runs cold; a fixed-order A/B lies), medians of
    `repeat`. The coalescing figure is mean caller-items-per-device-
    dispatch: the pipeline arm counts every caller item a wave settles
    (dedup riders included), the per-call arm counts the supervised
    verifier's real submitted items — both BEFORE padding."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from plenum_tpu.tools.local_pool import run_load

    arms = {"pipeline": "jax", "percall": "jax-percall"}
    # config7-style load shape for BOTH arms: a 32-deep trickle through
    # SMALL per-call-site batches (quota 16 — the shape ingress ticks
    # produce: many small per-tick auth batches per node) rather than the
    # headline's 256-deep flood. Per-call dispatches stay tick-sized
    # while the ring coalesces the same work across stages and co-hosted
    # nodes into RTT-sized waves — exactly the amortization the pipeline
    # exists to buy.
    overrides = {"LISTENER_MESSAGE_QUOTA": 16, "REMOTES_MESSAGE_QUOTA": 16}
    for b in arms.values():              # cold pass: compiles + warmup
        run_load(n_nodes=4, n_txns=40, backend=b, timeout=120.0,
                 config_overrides=overrides)
    runs: dict[str, list] = {k: [] for k in arms}
    for _ in range(repeat):
        for k, b in arms.items():        # interleaved
            runs[k].append(run_load(n_nodes=4, n_txns=n_txns, backend=b,
                                    timeout=120.0, window=32,
                                    config_overrides=overrides))

    def med(rs):
        good = sorted((r for r in rs if r.get("txns_ordered")),
                      key=lambda r: r["tps"])
        return good[len(good) // 2] if good else None

    pipe, percall = med(runs["pipeline"]), med(runs["percall"])
    out: dict = {"n_txns": n_txns, "repeat": repeat}
    if pipe is not None:
        out["pipeline_tps"] = pipe["tps"]
        out["pipeline_p50_ms"] = pipe.get("p50_latency_ms")
        ps = pipe.get("pipeline") or {}
        out["pipeline_items_per_dispatch"] = ps.get("items_per_dispatch")
        out["pipeline_dedup_ratio"] = ps.get("pipeline_dedup_ratio")
        out["pipeline_dispatches"] = ps.get("dispatches")
        out["pipeline_compiled_shapes"] = ps.get("compiled_shapes")
        out["pipeline_unpinned_shapes"] = ps.get("unpinned_shapes")
    if percall is not None:
        out["percall_tps"] = percall["tps"]
        out["percall_p50_ms"] = percall.get("p50_latency_ms")
        pc = percall.get("percall") or {}
        out["percall_items_per_dispatch"] = pc.get("items_per_dispatch")
        out["percall_dispatches"] = pc.get("device_batches")
    a = out.get("pipeline_items_per_dispatch")
    b = out.get("percall_items_per_dispatch")
    if a and b:
        out["coalescing_ratio"] = round(a / b, 2)
    return out


def config8_pipeline_ab(n_txns: int = 150,
                        timeout: float = 900.0) -> dict:
    """Pipelined-vs-per-call device A/B on JAX-ON-CPU, in a subprocess so
    the bench process itself never initialises a jax backend.
    This figure is a CPU measurement of the pipeline's host-side logic
    (JAX-on-CPU runs the same ring code the TPU runs) and says so:
    `platform: cpu` rides the row, and it never stands in for a device
    figure."""
    import os
    import subprocess
    import sys

    code = ("import json\n"
            "from plenum_tpu.tools.bench_configs import _pipeline_ab_inproc\n"
            f"print(json.dumps(_pipeline_ab_inproc(n_txns={n_txns})))\n")
    try:
        out = subprocess.run(
            [sys.executable, "-c", code],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": "pipeline A/B timed out"}
    for line in reversed(out.stdout.strip().splitlines() or [""]):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            parsed["platform"] = "cpu"
            return parsed
    return {"error": (out.stderr or "no output").strip()[-300:]}


def _multichip_ab_inproc(seconds: float = 6.0, bucket: int = 16,
                         n_devices: int = 8, repeat: int = 3) -> dict:
    """The multi-device crypto-pipeline A/B, run INSIDE a forced-8-CPU-
    device subprocess (config14_multichip spawns it): the SAME pipelined
    crypto-wave flood (PR 8's 256-deep shape: unique well-formed content,
    double-buffered, every wave padded to the pinned bucket) through

      (a) ONE device  — the PR 8 single-ring pipeline pinned to chip 0;
      (b) N devices   — the ring sharded into per-chip lanes, one
                        breakable supervised verifier per device.

    WARMED and INTERLEAVED per the PR 6/PR 8 methodology, medians of
    `repeat`. The figure is aggregate crypto-wave throughput (caller
    items settled per second) — the thing lane scale-out buys; per-lane
    dispatch counts ride along as placement provenance. Honesty note:
    on forced-host CPU devices each lane's kernel execution runs on the
    host's shared cores, so the measured scaling is the RING's ability
    to keep N execution streams busy (dispatch concurrency + double
    buffering), the same property that scales on real chips."""
    import random
    import time as _time

    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)

    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    from plenum_tpu.parallel.mesh import lane_roster
    from plenum_tpu.parallel.pipeline import (CryptoPipeline,
                                              make_multidevice_pipeline)
    from plenum_tpu.parallel.supervisor import supervise

    cfg = Config(PIPELINE_MIN_BUCKET=bucket, PIPELINE_MAX_BUCKET=bucket,
                 PIPELINE_FLUSH_WAIT=0.0)
    devs = lane_roster(n_devices)
    one = CryptoPipeline(
        ed_inner=supervise(JaxEd25519Verifier(min_batch=1,
                                              device=devs[0]),
                           label="lane0"),
        config=cfg)
    multi = make_multidevice_pipeline(cfg, n_devices, min_batch=1)
    for pipe in (one, multi):           # cold pass: compiles + warmup
        pipe.prewarm([bucket])
        pipe.pin()

    rng = random.Random(17)

    def junk(k):
        return [(rng.randbytes(16), rng.randbytes(63) + b"\x00",
                 rng.randbytes(32)) for _ in range(k)]

    def flood(pipe, lanes: int) -> float:
        settled = 0
        toks = []
        t0 = _time.perf_counter()
        deadline = t0 + seconds
        while _time.perf_counter() < deadline:
            toks.append(pipe.submit_verify(junk(bucket)))
            pipe.service()
            while len(toks) > 2 * lanes:
                if pipe.collect_verify(toks.pop(0), wait=True) is not None:
                    settled += bucket
        for tok in toks:
            if pipe.collect_verify(tok, wait=True) is not None:
                settled += bucket
        return settled / (_time.perf_counter() - t0)

    flood(one, 1)                       # warm the drive loop itself
    flood(multi, n_devices)
    ones, multis = [], []
    for _ in range(repeat):             # interleaved
        ones.append(flood(one, 1))
        multis.append(flood(multi, n_devices))
    ones.sort()
    multis.sort()
    one_med = ones[len(ones) // 2]
    multi_med = multis[len(multis) // 2]
    out = {
        "n_devices": n_devices, "bucket": bucket, "repeat": repeat,
        "one_device_items_per_s": round(one_med, 1),
        "multi_device_items_per_s": round(multi_med, 1),
        "scaling": round(multi_med / one_med, 2) if one_med else None,
        "per_device_dispatches": {
            "lane%d" % d["lane"]: d["dispatches"]
            for d in multi.device_state()},
        "one_device_dispatches": one.stats["dispatches"],
        "unpinned_shapes": (one.stats["unpinned_shapes"]
                            + multi.stats["unpinned_shapes"]),
    }
    multi.close()
    return out


def config14_multichip(seconds: float = 6.0,
                       timeout: float = 1500.0) -> dict:
    """N-device pipelined-flood A/B on JAX-ON-CPU (8 forced host
    devices), in a subprocess so the bench process never reconfigures
    its own jax backend. Published with `platform: cpu` and the
    per-device dispatch counts — a CPU measurement of the lane code's
    dispatch concurrency, not a device figure."""
    import os
    import subprocess
    import sys

    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "flags = os.environ.get('XLA_FLAGS', '')\n"
        "if 'xla_force_host_platform_device_count' not in flags:\n"
        "    os.environ['XLA_FLAGS'] = (flags +"
        " ' --xla_force_host_platform_device_count=8').strip()\n"
        "import json\n"
        "from plenum_tpu.tools.bench_configs import _multichip_ab_inproc\n"
        f"print(json.dumps(_multichip_ab_inproc(seconds={seconds})))\n")
    try:
        out = subprocess.run(
            [sys.executable, "-c", code],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": "multichip A/B timed out"}
    for line in reversed(out.stdout.strip().splitlines() or [""]):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            parsed["platform"] = "cpu"
            return parsed
    return {"error": (out.stderr or "no output").strip()[-300:]}


def _federation_ab_inproc(seconds: float = 6.0, bucket: int = 16,
                          n_hosts: int = 1, repeat: int = 3) -> dict:
    """Cross-host crypto-federation A/B (config17_federation spawns it
    in a subprocess): the SAME pipelined crypto-wave flood through

      (a) local-only — the PR 8 single-ring pipeline on this process's
          chip 0 (the arm a node runs when PIPELINE_REMOTE_HOSTS is
          unset);
      (b) federated  — the same local lane PLUS `n_hosts` RENTED crypto
          hosts: real `crypto_service` worker subprocesses, rostered
          over the wire as extra lanes with prewarm/pin negotiated up
          front and work-stealing balancing the backlog.

    WARMED and INTERLEAVED per the PR 6/PR 8 methodology, medians of
    `repeat`. The figure is aggregate items settled per second — what
    renting a host buys; per-host dispatch counts, steal counters and
    the remote ship p95 ride along as placement provenance. Honesty
    note: the rented workers run the NATIVE-LIBRARY backend, standing
    in for a host whose engine outruns the renting node's jax-on-cpu
    lane — the reason to rent at all (a TPU-backed fleet vs a CPU
    node). On a multi-core runner they also add genuine process-level
    parallelism; `host_cores` rides the row so a single-core runner's
    figure can never masquerade as core scale-out."""
    import os
    import random
    import subprocess
    import sys
    import tempfile
    import time as _time

    import jax
    jax.config.update("jax_platforms", "cpu")

    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    from plenum_tpu.parallel.federation import make_federated_pipeline
    from plenum_tpu.parallel.mesh import lane_roster
    from plenum_tpu.parallel.pipeline import CryptoPipeline
    from plenum_tpu.parallel.supervisor import supervise

    cfg = Config(PIPELINE_MIN_BUCKET=bucket, PIPELINE_MAX_BUCKET=bucket,
                 PIPELINE_FLUSH_WAIT=0.0,
                 PIPELINE_STEAL_THRESHOLD=bucket,
                 PIPELINE_STEAL_COOLDOWN=0.02)
    tmp = tempfile.mkdtemp(prefix="plenum-fed-bench-")
    hosts: list[str] = []
    procs: list = []
    fed = None
    try:
        for j in range(n_hosts):
            path = os.path.join(tmp, "host%d.sock" % j)
            procs.append(subprocess.Popen(
                [sys.executable, "-m",
                 "plenum_tpu.parallel.crypto_service",
                 "--socket", path, "--backend", "cpu",
                 "--min-batch", "1"],
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
            hosts.append(path)
        deadline = _time.monotonic() + 60.0
        for path in hosts:
            while not os.path.exists(path):
                if _time.monotonic() > deadline:
                    raise RuntimeError("crypto host %s never came up"
                                       % path)
                _time.sleep(0.05)

        local = CryptoPipeline(
            ed_inner=supervise(JaxEd25519Verifier(min_batch=1,
                                                  device=lane_roster(1)[0]),
                               label="lane0"),
            config=cfg)
        fed = make_federated_pipeline(cfg, min_batch=1, hosts=hosts,
                                      n_devices=1)
        for pipe in (local, fed):       # cold pass: compiles BOTH sides
            pipe.prewarm([bucket])      # of the wire before measuring
            pipe.pin()

        rng = random.Random(17)

        def junk(k):
            return [(rng.randbytes(16), rng.randbytes(63) + b"\x00",
                     rng.randbytes(32)) for _ in range(k)]

        def flood(pipe, lanes: int) -> float:
            # READY-ORDER drain, not FIFO: a blocking collect on the
            # oldest token would head-of-line block the fast local lane
            # behind every wire round trip, measuring the latency of
            # one remote wave instead of the throughput of the fleet
            settled = 0
            toks = []
            t0 = _time.perf_counter()
            deadline = t0 + seconds
            while _time.perf_counter() < deadline:
                toks.append(pipe.submit_verify(junk(bucket)))
                pipe.service()
                if len(toks) >= 4 * lanes:
                    still = []
                    for tok in toks:
                        if pipe.collect_verify(tok,
                                               wait=False) is not None:
                            settled += bucket
                        else:
                            still.append(tok)
                    toks = still
                while len(toks) > 6 * lanes:    # bounded backpressure
                    if pipe.collect_verify(toks.pop(0),
                                           wait=True) is not None:
                        settled += bucket
            for tok in toks:
                if pipe.collect_verify(tok, wait=True) is not None:
                    settled += bucket
            return settled / (_time.perf_counter() - t0)

        n_lanes = 1 + n_hosts
        flood(local, 1)                 # warm the drive loop itself
        flood(fed, n_lanes)
        locals_, feds = [], []
        for _ in range(repeat):         # interleaved
            locals_.append(flood(local, 1))
            feds.append(flood(fed, n_lanes))
        locals_.sort()
        feds.sort()
        local_med = locals_[len(locals_) // 2]
        fed_med = feds[len(feds) // 2]
        fed_state = fed.federation_state()
        out = {
            "n_hosts": n_hosts, "bucket": bucket, "repeat": repeat,
            "host_cores": os.cpu_count(),
            "local_items_per_s": round(local_med, 1),
            "federated_items_per_s": round(fed_med, 1),
            "scaling": (round(fed_med / local_med, 2)
                        if local_med else None),
            "per_host_dispatches": {
                (d.get("host") or "local%d" % d["lane"]): d["dispatches"]
                for d in fed.device_state()},
            "steals": fed.stats["steals"],
            "stolen_items": fed.stats["stolen_items"],
            "ship_ms_p95": fed_state["ship_ms_p95"],
            "unpinned_shapes": (local.stats["unpinned_shapes"]
                                + fed.stats["unpinned_shapes"]),
            "scaling_target": 1.7,
        }
        if (os.cpu_count() or 1) < 2:
            out["scaling_note"] = (
                "single-core runner: the local lane and the rented "
                "host share ONE core, so the A/B measures the "
                "federation machinery (latency-aware placement, "
                "stealing, wire, zero double-verifies) at capacity "
                "parity, not core scale-out; the >=1.7x target needs "
                "a multi-core runner or a real fleet")
        fed.close()
        fed = None
        return out
    finally:
        if fed is not None:
            try:
                fed.close()
            except Exception:
                pass
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)


def config17_federation(seconds: float = 6.0,
                        timeout: float = 1500.0) -> dict:
    """Local-only vs local+1-rented-crypto-host flood A/B on JAX-ON-CPU,
    in a subprocess so the bench process never reconfigures its own jax
    backend (the rented host is a further subprocess — a real separate
    interpreter reached over the crypto_service wire). Published with
    `platform: cpu` plus per-host dispatch and steal counts — a CPU
    measurement of the lane/wire code a real fleet runs against
    TPU-backed hosts, not a device figure."""
    import os
    import subprocess
    import sys

    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import json\n"
        "from plenum_tpu.tools.bench_configs import _federation_ab_inproc\n"
        f"print(json.dumps(_federation_ab_inproc(seconds={seconds})))\n")
    try:
        out = subprocess.run(
            [sys.executable, "-c", code],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": "federation A/B timed out"}
    for line in reversed(out.stdout.strip().splitlines() or [""]):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            parsed["platform"] = "cpu"
            return parsed
    return {"error": (out.stderr or "no output").strip()[-300:]}


def _ordered_path_ab_inproc(n_txns: int = 100, repeat: int = 3,
                            n_devices: int = 4) -> dict:
    """Fused-commit-wave vs host-recommit A/B on the FULL write path
    (config16_ordered_path spawns it inside a forced-N-CPU-device
    subprocess): the SAME 4-node NYM write load through

      (a) fused — COMMIT_WAVE on: each ordered batch's triple-root
          recommit (state head + ledger append + audit append) rides
          the shared ring's cmt lane, level sweeps deduped across the
          co-hosted replicas and flushed as pinned pow-2 waves;
      (b) host  — COMMIT_WAVE off: every replica resolves every root
          inline (per-node sha3/RLP and shadow-tree loops), the
          pre-wave path.

    WARMED and INTERLEAVED per the PR 6/PR 8 methodology, medians of
    `repeat`. The figure is ordered-path TPS (client submit -> first
    REPLY), NOT crypto items/s — VaultxGPU's per-phase attribution
    point; the commit_stage percentiles (apply vs commit_wave) ride
    along so the delta localizes to the recommit stage, and the pinned
    ladder must close the run with 0 unpinned cmt shapes."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from plenum_tpu.tools.local_pool import run_load

    arms = {"fused": {"COMMIT_WAVE": True},
            "host": {"COMMIT_WAVE": False}}
    base = {"PIPELINE_DEVICES": n_devices}
    for ov in arms.values():             # cold pass: compiles + warmup
        run_load(n_nodes=4, n_txns=30, backend="jax", timeout=180.0,
                 config_overrides=dict(base, **ov))
    runs: dict[str, list] = {k: [] for k in arms}
    for _ in range(repeat):
        for k, ov in arms.items():       # interleaved
            runs[k].append(run_load(n_nodes=4, n_txns=n_txns,
                                    backend="jax", timeout=240.0,
                                    config_overrides=dict(base, **ov)))

    def med(rs):
        good = sorted((r for r in rs if r.get("txns_ordered")),
                      key=lambda r: r["tps"])
        return good[len(good) // 2] if good else None

    fused, host = med(runs["fused"]), med(runs["host"])
    out: dict = {"n_txns": n_txns, "repeat": repeat,
                 "n_devices": n_devices}
    if fused is not None:
        out["fused_tps"] = fused["tps"]
        out["fused_p50_ms"] = fused.get("p50_latency_ms")
        ps = fused.get("pipeline") or {}
        cmt = ps.get("cmt") or {}
        out["commit_waves"] = cmt.get("waves")
        out["commit_wave_levels"] = cmt.get("levels")
        out["commit_wave_host_fallbacks"] = cmt.get("host_fallbacks")
        out["fused_unpinned_shapes"] = ps.get("unpinned_shapes")
        out["per_device_dispatches"] = {
            "lane%d" % d["lane"]: d["dispatches"]
            for d in ps.get("devices", [])}
        stage = fused.get("commit_stage") or {}
        out["fused_commit_wave_ms_p50"] = stage.get("commit_wave_ms_p50")
        out["fused_apply_ms_p50"] = stage.get("apply_ms_p50")
    if host is not None:
        out["host_tps"] = host["tps"]
        out["host_p50_ms"] = host.get("p50_latency_ms")
        stage = host.get("commit_stage") or {}
        out["host_apply_ms_p50"] = stage.get("apply_ms_p50")
    if out.get("fused_tps") and out.get("host_tps"):
        out["ordered_path_speedup"] = round(
            out["fused_tps"] / out["host_tps"], 2)
    return out


def config16_ordered_path(n_txns: int = 100,
                          timeout: float = 1800.0) -> dict:
    """Ordered-path fused-vs-host recommit A/B on JAX-ON-CPU (4 forced
    host devices), in a subprocess so
    the bench process never reconfigures its own jax backend. Published
    with `platform: cpu` and the per-device dispatch counts — a CPU
    measurement of the wave code, not a device figure."""
    import os
    import subprocess
    import sys

    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "flags = os.environ.get('XLA_FLAGS', '')\n"
        "if 'xla_force_host_platform_device_count' not in flags:\n"
        "    os.environ['XLA_FLAGS'] = (flags +"
        " ' --xla_force_host_platform_device_count=4').strip()\n"
        "import json\n"
        "from plenum_tpu.tools.bench_configs import _ordered_path_ab_inproc\n"
        f"print(json.dumps(_ordered_path_ab_inproc(n_txns={n_txns})))\n")
    try:
        out = subprocess.run(
            [sys.executable, "-c", code],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": "ordered-path A/B timed out"}
    for line in reversed(out.stdout.strip().splitlines() or [""]):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            parsed["platform"] = "cpu"
            return parsed
    return {"error": (out.stderr or "no output").strip()[-300:]}


def config1b_distinct_signers(n_txns: int = 200,
                              timeout: float = 120.0) -> dict:
    """Diverse-client honesty datum: every write signed by a DIFFERENT
    key. The headline configs sign everything with one trustee key,
    which maximally amortizes verkey parsing/decompression and the
    co-hosted verdict caches across hops (one content per request is
    still unique, but a single signer is the cache-friendliest shape).
    Here, phase 1 creates n DIDs (trustee-signed NYMs), phase 2 has
    each DID owner-sign an ATTRIB on itself — n distinct verkeys on the
    authentication hot path. Reported tps covers phase 2 only."""
    import json as _json

    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import ATTRIB, NYM

    try:
        (names, nodes, timer, trustee,
         replies, ReplyCls, DOMAIN, plane, net) = lp.build_pool(4, "cpu")
        users = [Ed25519Signer(seed=(b"ds%08d" % i).ljust(32, b"\0")[:32])
                 for i in range(n_txns)]
        nyms = []
        for i, u in enumerate(users):
            req = Request(trustee.identifier, i + 1,
                          {"type": NYM, "dest": u.identifier,
                           "verkey": u.verkey_b58})
            req.signature = trustee.sign_b58(req.signing_bytes())
            nyms.append(req)
        done, _ = _drive_inprocess(names, nodes, timer, replies, ReplyCls,
                                   plane, nyms, timeout)
        if done < n_txns:
            return {"error": f"setup incomplete: {done}/{n_txns} NYMs"}
        attribs = []
        for i, u in enumerate(users):
            req = Request(u.identifier, 1,
                          {"type": ATTRIB, "dest": u.identifier,
                           "raw": _json.dumps({"endpoint": str(i)})})
            req.signature = u.sign_b58(req.signing_bytes())
            attribs.append(req)
        done, dt = _drive_inprocess(names, nodes, timer, replies, ReplyCls,
                                    plane, attribs, timeout)
        return {"txns_ordered": done, "txns_requested": n_txns,
                "distinct_signers": n_txns,
                "tps": round(done / dt, 1) if dt else 0.0}
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def config11_telemetry(n_txns: int = 150, timeout: float = 120.0) -> dict:
    """Telemetry-plane acceptance on the bench line (docs/observability.md
    "Live fleet telemetry"):

    1. **Overhead A/B** — the SAME 4-node cpu write load with the
       telemetry plane enabled vs disabled (NULL_TELEMETRY fast path),
       WARMED and INTERLEAVED medians of 3 per the config5/config8
       methodology. The budget is the tracing plane's: <=2% (the
       disabled path is one attribute check, microbench-pinned in
       tests/test_telemetry.py; this publishes the measured end-to-end
       figure, which rides the host's single-run noise band).
    2. **Burn-rate / imbalance columns** — a sim-time 2-shard fabric
       under a zipfian-hot write mix (90% of writes key into one
       shard): the aggregator's load-imbalance index must flag the hot
       shard, and the burn/health summaries ride along.
    """
    from plenum_tpu.tools.local_pool import run_load

    try:
        arms = {"on": {"TELEMETRY": True}, "off": {"TELEMETRY": False}}
        for ov in arms.values():                 # cold pass: warmup
            run_load(n_nodes=4, n_txns=40, backend="cpu", timeout=timeout,
                     config_overrides=ov)
        # 5 interleaved repeats (vs the usual 3): the expected delta is
        # ~0 (the emitter works once per TELEMETRY_INTERVAL, not per
        # txn), so the A/B is measuring inside the host-noise band and
        # needs the tighter median
        runs: dict[str, list] = {k: [] for k in arms}
        for _ in range(5):
            for k, ov in arms.items():           # interleaved
                runs[k].append(run_load(n_nodes=4, n_txns=n_txns,
                                        backend="cpu", timeout=timeout,
                                        config_overrides=ov))

        def med(rs):
            good = sorted((r for r in rs if r.get("txns_ordered")),
                          key=lambda r: r["tps"])
            return good[len(good) // 2] if good else None

        on, off = med(runs["on"]), med(runs["off"])
        out: dict = {"n_txns": n_txns}
        if on is not None and off is not None and off.get("tps"):
            out["telemetry_on_tps"] = on["tps"]
            out["telemetry_off_tps"] = off["tps"]
            out["telemetry_overhead_pct"] = round(
                100 * (1 - on["tps"] / off["tps"]), 1)

        # hot-shard arm: sim-time fabric, zipfian-hot key mix
        out.update(_telemetry_hot_shard_arm())
        return out
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def _telemetry_hot_shard_arm(n_txns: int = 120) -> dict:
    """Deterministic sim-time 2-shard fabric under a 90:10 hot-key skew;
    -> the aggregator's imbalance/burn/health columns."""
    from plenum_tpu.common.request import Request
    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM
    from plenum_tpu.shards import ShardedSimFabric

    fab = ShardedSimFabric(
        n_shards=2, nodes_per_shard=3, seed=17,
        config=Config(Max3PCBatchWait=0.05, TELEMETRY_INTERVAL=0.5,
                      STATE_FRESHNESS_UPDATE_INTERVAL=600.0))
    by_shard: dict[int, list] = {0: [], 1: []}
    i = 0
    while min(len(v) for v in by_shard.values()) < n_txns and i < 8 * n_txns:
        i += 1
        user = Ed25519Signer(seed=(b"tz%08d" % i).ljust(32, b"\0")[:32])
        req = Request(fab.trustee.identifier, i,
                      {"type": NYM, "dest": user.identifier,
                       "verkey": user.verkey_b58})
        req.signature = fab.trustee.sign_b58(req.signing_bytes())
        sid = fab.router.shard_of(req)
        if sid in by_shard:
            by_shard[sid].append(req)
    hot, cold = by_shard[0], by_shard[1]
    # 90:10 zipfian-shaped skew onto shard 0
    for j in range(n_txns):
        fab.submit_write(hot[j] if j % 10 else cold[j // 10])
        if j % 16 == 15:
            fab.run(1.0)
    fab.run(10.0)
    fab.ordered_counts()
    index, hot_sid = fab.aggregator.load_imbalance()
    s = fab.aggregator.fleet_summary()
    return {
        "imbalance_index": index,
        "hot_shard": hot_sid,
        "ordered_rates": s["ordered_rates"],
        "shard_health": s["shard_health"],
        "burn": {k: v for k, v in s["burn"].items()},
        "alerts": len(s["alerts"]),
    }


def config12_reshard(n_users: int = 320, phase_s: float = 20.0) -> dict:
    """Elastic-resharding acceptance on the bench line (docs/sharding.md
    "Elastic resharding"): a deterministic sim-time 2-shard fabric under
    a zipfian hot-range workload (90% of writes key into shard 0). The
    PR 11 aggregator flags the hot shard, ``maybe_split`` consumes the
    signal and live-splits the hot range onto a new sub-pool UNDER the
    same load, and the run publishes:

    * pre/post aggregate TPS (sim-time) and the recovery ratio — the
      acceptance gate is post >= 0.8 * pre within the run;
    * the load-imbalance index before (hot flagged) and after (below
      ``SHARD_IMBALANCE_THRESHOLD``);
    * the migration ledger: txns copied, handoff forwards, epoch.
    """
    from plenum_tpu.common.request import Request
    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM
    from plenum_tpu.shards import ShardedSimFabric

    try:
        config = Config(Max3PCBatchWait=0.05, TELEMETRY_INTERVAL=0.5,
                        SLO_BURN_SLOW_WINDOW=30.0,
                        STATE_FRESHNESS_UPDATE_INTERVAL=600.0)
        fab = ShardedSimFabric(n_shards=2, nodes_per_shard=3, seed=23,
                               config=config)
        # mine the zipfian request pools: 90% hot (shard 0), 10% cold
        # the pools must outlast all three driven phases (the zipfian
        # cursor advancing past the hot pool's end would fake a post-
        # reshard skew flip)
        hot, cold = [], []
        i = 0
        while (len(hot) < n_users or len(cold) < n_users // 6) \
                and i < 12 * n_users:
            i += 1
            u = Ed25519Signer(seed=(b"rz%08d" % i).ljust(32, b"\0")[:32])
            req = Request(fab.trustee.identifier, i,
                          {"type": NYM, "dest": u.identifier,
                           "verkey": u.verkey_b58})
            req.signature = fab.trustee.sign_b58(req.signing_bytes())
            (hot if fab.router.shard_of(req) == 0 else cold).append(req)

        cursor = {"h": 0, "c": 0, "n": 0}

        def drive(seconds: float) -> float:
            """Zipfian-paced submission; -> ordered txns per SIM second."""
            t0 = fab.timer.get_current_time()
            base = sum(s.ordered_count() for s in fab.shards.values())
            steps = int(seconds / 0.25)
            for k in range(steps):
                cursor["n"] += 1
                if cursor["n"] % 10 and cursor["h"] < len(hot):
                    fab.submit_write(hot[cursor["h"]])
                    cursor["h"] += 1
                elif cursor["c"] < len(cold):
                    fab.submit_write(cold[cursor["c"]])
                    cursor["c"] += 1
                fab.run(0.25)
                fab.ordered_counts()
            dt = fab.timer.get_current_time() - t0
            done = sum(s.ordered_count()
                       for s in fab.shards.values()) - base
            return round(done / dt, 2) if dt else 0.0

        pre_tps = drive(phase_s)
        index_before, hot_sid = fab.aggregator.load_imbalance()
        m = fab.reshard.maybe_split()          # consume the PR 11 signal
        if m is None:
            return {"error": f"imbalance signal never flagged the hot "
                             f"shard (index={index_before})"}
        during_tps = drive(phase_s)            # reshard runs under load
        elapsed = 0.0
        while m.phase not in ("done", "aborted") and elapsed < 120.0:
            fab.run(0.5)
            elapsed += 0.5
        # the post phase runs 2x so the imbalance window judges a sample
        # big enough that a 72-write binomial wobble cannot re-flag a
        # healthily split range
        post_tps = drive(2 * phase_s)          # post-reshard steady state
        index_after, hot_after = fab.aggregator.load_imbalance()
        return {
            "pre_tps": pre_tps,
            "during_tps": during_tps,
            "post_tps": post_tps,
            "recovery_ratio": round(post_tps / pre_tps, 2)
            if pre_tps else None,
            "imbalance_before": index_before,
            "hot_shard_flagged": hot_sid,
            "imbalance_after": index_after,
            "hot_shard_after": hot_after,
            "imbalance_threshold": config.SHARD_IMBALANCE_THRESHOLD,
            "migration": m.to_dict(),
            "epoch": fab.mapping.epoch,
            "shards_after": len(fab.shards),
            "stale_nacks": len(fab.stale_nacks),
        }
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def config13_commitment(page_size: int = 16, n_dids: int = 48,
                        n_pages: int = 8, timeout: float = 90.0) -> dict:
    """Proof-size / verify-time A/B between the two state-commitment
    backends (docs/state_commitment.md): the SAME 4-node pool + DID set,
    once with STATE_COMMITMENT=mpt and once =verkle.

    Measures, per arm:

    * a 16-key client page as ONE envelope (`ReadPlane.page_envelope` —
      Verkle aggregates the whole page into one opening; MPT's baseline
      is the honest per-key sibling chains), bytes from the PRODUCTION
      proof-byte counters (read_plane.proof_bytes_*), client verify
      p50/p95 over `verify_page_envelope`;
    * single verified GET_NYM reads through the ordinary ladder
      (driver verify p50/p95 + per-envelope bytes);
    * the expected transfer time of one page over the ``lossy_wan``
      inter-region link profile (2.5e6 B/s, 3% loss -> x1/(1-p)
      expected retransmission bytes) — the bytes-are-the-product
      framing for WAN clients.

    Arms run INTERLEAVED with one discarded warm-up and medians of 3
    (the bench-host contention lesson from config5).
    """
    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.common.metrics import MetricsName, percentile
    from plenum_tpu.common.request import Request
    from plenum_tpu.common.serialization import pack
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import GET_NYM, NYM
    from plenum_tpu.reads import SimReadDriver
    from plenum_tpu.reads.proofs import verify_page_envelope

    LOSSY_BW = 2.5e6                 # bytes/s (lossy_wan inter-region)
    LOSSY_LOSS = 0.03

    def one_arm(backend: str) -> dict:
        (names, nodes, timer, trustee,
         replies, ReplyCls, DOMAIN, plane, net) = lp.build_pool(
             4, "cpu", config_overrides={"STATE_COMMITMENT": backend})
        users, setup = [], []
        for i in range(n_dids):
            u = Ed25519Signer(seed=(b"c13%05d" % i).ljust(32, b"\0")[:32])
            users.append(u)
            req = Request(trustee.identifier, i + 1,
                          {"type": NYM, "dest": u.identifier,
                           "verkey": u.verkey_b58})
            req.signature = trustee.sign_b58(req.signing_bytes())
            setup.append(req)
        done, _ = _drive_inprocess(names, nodes, timer, replies, ReplyCls,
                                   plane, setup, timeout)
        if done < len(setup):
            return {"error": f"{backend}: ordered {done}/{len(setup)}"}
        bls_keys = lp.pool_bls_keys(names)
        node = nodes[names[0]]

        # --- single reads through the verified ladder ---
        def submit(name, req):
            nodes[name].handle_client_message(req.to_dict(), "c13")

        def collect(name):
            out = [m.result for _, m, c in replies[name]
                   if isinstance(m, ReplyCls) and c == "c13"]
            replies[name].clear()
            return out

        def pump(seconds):
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                timer.service()
                for nd in nodes.values():
                    nd.prod()

        driver = SimReadDriver(submit, collect, pump, names, bls_keys,
                               freshness_s=1e12,
                               now=timer.get_current_time)
        served = 0
        for i, u in enumerate(users[:page_size]):
            q = Request("c13r", i + 1,
                        {"type": GET_NYM, "dest": u.identifier})
            if driver.read(q, per_node_s=2.0, step_s=0.001) is not None:
                served += 1
        s = driver.stats.summary()

        # --- the 16-key page as ONE envelope ---
        page_keys = [u.identifier.encode() for u in users[:page_size]]
        gen_s: list[float] = []
        env = None
        for _ in range(n_pages):
            t0 = time.perf_counter()
            env = node.read_plane.page_envelope(DOMAIN, page_keys)
            gen_s.append(time.perf_counter() - t0)
        if env is None:
            return {"error": f"{backend}: page envelope unanchorable"}
        page_bytes = len(pack(env))
        ver_s: list[float] = []
        for _ in range(n_pages):
            t0 = time.perf_counter()
            ok, values, why = verify_page_envelope(
                env, page_keys, bls_keys, DOMAIN, freshness_s=1e12,
                now=timer.get_current_time)
            ver_s.append(time.perf_counter() - t0)
            if not ok:
                return {"error": f"{backend}: page verify failed ({why})"}

        # production proof-byte counters (the satellite contract: the
        # A/B reads what the node actually sampled, not a bench tally)
        metric = (MetricsName.READ_PROOF_BYTES_VERKLE_MULTI
                  if backend == "verkle"
                  else MetricsName.READ_PROOF_BYTES_STATE_MULTI)
        acc = node.metrics.accumulators.get(metric)
        counter_bytes = None
        if acc is not None and acc.samples:
            counter_bytes = {
                "p50": int(percentile(acc.samples, 0.5)),
                "p95": int(percentile(acc.samples, 0.95)),
            }
        transfer_ms = page_bytes / LOSSY_BW / (1 - LOSSY_LOSS) * 1000
        return {
            "singles_served": served,
            "single_verify_ms_p50": s.get("verify_ms_p50"),
            "single_verify_ms_p95": s.get("verify_ms_p95"),
            "page_bytes": page_bytes,
            "bytes_per_read": round(page_bytes / page_size, 1),
            "page_gen_ms_p50": round(
                percentile(gen_s, 0.5) * 1000, 2),
            "page_verify_ms_p50": round(
                percentile(ver_s, 0.5) * 1000, 2),
            "page_verify_ms_p95": round(
                percentile(ver_s, 0.95) * 1000, 2),
            "proof_bytes_counter": counter_bytes,
            "lossy_wan_page_transfer_ms": round(transfer_ms, 2),
        }

    try:
        one_arm("mpt")                           # warm-up, discarded
        runs = {"mpt": [], "verkle": []}
        for _ in range(3):                       # interleaved
            for backend in ("mpt", "verkle"):
                arm = one_arm(backend)
                if "error" in arm:
                    return arm
                runs[backend].append(arm)
        out: dict = {"page_size": page_size, "n_dids": n_dids}
        for backend in ("mpt", "verkle"):
            arms = sorted(runs[backend],
                          key=lambda a: a["page_verify_ms_p50"])
            out[backend] = arms[1]               # median by verify time
        out["bytes_reduction"] = round(
            out["mpt"]["page_bytes"] / out["verkle"]["page_bytes"], 2)
        # TS-Verkle-derived client budget (docs/state_commitment.md):
        # per-page = 2 pairings + one MSM over <= page*depth openings
        out["verify_budget_ms_p95"] = 60.0
        out["verify_within_budget"] = (
            out["verkle"]["page_verify_ms_p95"]
            <= out["verify_budget_ms_p95"])
        return out
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def config18_autopilot(n_users: int = 320, phase_s: float = 20.0) -> dict:
    """Hands-off heal of the config12 zipfian hot-range flood
    (docs/robustness.md "Autopilot"): the SAME 2-shard fabric and
    90%-hot workload, but ``AUTOPILOT=True`` and the driver never
    touches the control plane — no ``maybe_split`` call, no lane
    pokes, zero test-driven actuation. The autopilot's reshard policy
    must flag the sustained imbalance on its own cadence and live-
    split the hot range UNDER the flood (possibly already inside the
    first phase: the control plane acts as soon as the signal
    sustains, it does not wait for the driver's phase boundaries).

    * pre/post aggregate TPS and the recovery ratio — the acceptance
      gate is post >= 0.8 * pre, same bar as config12;
    * the control ledger (reserved CONTROL_LEDGER_ID txns) with the
      split decision's seq/time and its full audit
      (tools/control_audit.py) — must lint clean;
    * the migration ledger, exactly as config12 reports it.
    """
    from plenum_tpu.common.request import Request
    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM
    from plenum_tpu.shards import ShardedSimFabric
    from plenum_tpu.tools.control_audit import audit_records

    try:
        # generous batch/ingress SLOs: this run grades the RESHARD
        # policy end-to-end; the degradation ladder has its own fuzz
        # scenario and must not park the pool read-only over sim-time
        # batching noise mid-split
        config = Config(Max3PCBatchWait=0.05, TELEMETRY_INTERVAL=0.5,
                        SLO_BURN_SLOW_WINDOW=30.0,
                        STATE_FRESHNESS_UPDATE_INTERVAL=600.0,
                        AUTOPILOT=True, AUTOPILOT_INTERVAL=0.5,
                        BATCH_SLO_P95=30.0, INGRESS_SLO_P95=30.0)
        fab = ShardedSimFabric(n_shards=2, nodes_per_shard=3, seed=23,
                               config=config)
        hot, cold = [], []
        i = 0
        while (len(hot) < n_users or len(cold) < n_users // 6) \
                and i < 12 * n_users:
            i += 1
            u = Ed25519Signer(seed=(b"rz%08d" % i).ljust(32, b"\0")[:32])
            req = Request(fab.trustee.identifier, i,
                          {"type": NYM, "dest": u.identifier,
                           "verkey": u.verkey_b58})
            req.signature = fab.trustee.sign_b58(req.signing_bytes())
            (hot if fab.router.shard_of(req) == 0 else cold).append(req)

        cursor = {"h": 0, "c": 0, "n": 0}

        def drive(seconds: float) -> float:
            t0 = fab.timer.get_current_time()
            base = sum(s.ordered_count() for s in fab.shards.values())
            steps = int(seconds / 0.25)
            for k in range(steps):
                cursor["n"] += 1
                if cursor["n"] % 10 and cursor["h"] < len(hot):
                    fab.submit_write(hot[cursor["h"]])
                    cursor["h"] += 1
                elif cursor["c"] < len(cold):
                    fab.submit_write(cold[cursor["c"]])
                    cursor["c"] += 1
                fab.run(0.25)
                fab.ordered_counts()
            dt = fab.timer.get_current_time() - t0
            done = sum(s.ordered_count()
                       for s in fab.shards.values()) - base
            return round(done / dt, 2) if dt else 0.0

        pre_tps = drive(phase_s)               # flood onset
        index_flood, hot_sid = fab.aggregator.load_imbalance()
        during_tps = drive(phase_s)            # autopilot acts in here
        elapsed = 0.0                          # run any migration out
        while fab.reshard.active is not None and elapsed < 120.0:
            fab.run(0.5)
            elapsed += 0.5
        post_tps = drive(2 * phase_s)          # post-heal steady state
        index_after, hot_after = fab.aggregator.load_imbalance()
        records = fab.autopilot.ledger.to_dicts()
        splits = [r for r in records if r["action"] == "split"]
        if not splits:
            return {"error": "the autopilot never split the hot shard "
                             f"(imbalance={index_flood}, "
                             f"records={len(records)})"}
        m = fab.reshard.history[0] if fab.reshard.history else None
        return {
            "pre_tps": pre_tps,
            "during_tps": during_tps,
            "post_tps": post_tps,
            "recovery_ratio": round(post_tps / pre_tps, 2)
            if pre_tps else None,
            "imbalance_flood": index_flood,
            "hot_shard_flagged": hot_sid,
            "imbalance_after": index_after,
            "hot_shard_after": hot_after,
            "test_driven_actuations": 0,       # by construction
            "split_seq": splits[0]["seq"],
            "split_t": splits[0]["t"],
            "split_evidence": splits[0]["evidence"],
            "control_records": len(records),
            "control_holds": sum(1 for r in records
                                 if r["action"] == "hold"),
            "audit_problems": audit_records(records),
            "migration": m.to_dict() if m is not None else None,
            "epoch": fab.mapping.epoch,
            "shards_after": len(fab.shards),
            "autopilot": fab.autopilot.summary(),
        }
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def config19_edge(n_reads: int = 1800, write_every: int = 20,
                  timeout: float = 120.0) -> dict:
    """The Proof CDN under a 95:5 read:write flood (docs/edge.md): the
    config6 pool with ONE keyless edge cache (reads/edge.py) in front —
    every read walks the edge-first ladder and verifies client-side.
    Reports the edge hit-rate and the client-facing edge service rate
    (the acceptance bar: >95% of verified reads served by edges), the
    POOL read load left behind (validator-served reads + CDN origin
    refills — what the edge tier exists to keep near zero), bytes per
    edge-served read, client verify p95, and `platform: cpu` (the
    pool's crypto plane is the jax-on-cpu pipeline build_pool
    compiles)."""
    import plenum_tpu.tools.local_pool as lp
    from plenum_tpu.common.node_messages import BatchCommitted
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import GET_NYM, NYM
    from plenum_tpu.reads import SimEdge, SimReadDriver

    try:
        (names, nodes, timer, trustee,
         replies, ReplyCls, DOMAIN, plane, net) = lp.build_pool(4, "cpu")
        users = []
        setup = []
        for i in range(20):
            user = Ed25519Signer(seed=(b"ed%08d" % i).ljust(32, b"\0")[:32])
            users.append(user)
            req = Request(trustee.identifier, i + 1,
                          {"type": NYM, "dest": user.identifier,
                           "verkey": user.verkey_b58})
            req.signature = trustee.sign_b58(req.signing_bytes())
            setup.append(req)
        done, _ = _drive_inprocess(names, nodes, timer, replies, ReplyCls,
                                   plane, setup, 60.0)
        if done < len(setup):
            return {"error": f"setup ordered only {done}/{len(setup)}"}

        rr = {"i": 0}

        def origin(request):
            name = names[rr["i"] % len(names)]
            rr["i"] += 1
            return nodes[name].read_plane.answer(request)

        edge = SimEdge("edge1", origin, now=timer.get_current_time,
                       freshness_s=1e9)
        edge.register(lambda v, msg: nodes[v]
                      .handle_client_message(msg, edge.client_id), names)

        def route_pushes(name):
            keep = []
            for t, m, c in replies[name]:
                if c == edge.client_id:
                    if isinstance(m, BatchCommitted):
                        edge.deliver_push(m, name)
                else:
                    keep.append((t, m, c))
            replies[name][:] = keep

        def submit(name, req):
            if name == edge.name:
                edge.handle_client_message(req.to_dict(), "rdr")
            else:
                nodes[name].handle_client_message(req.to_dict(), "rdr")

        def collect(name):
            if name == edge.name:
                out = [m.result for m, _ in edge.sent
                       if isinstance(m, ReplyCls)]
                edge.sent.clear()
                return out
            route_pushes(name)
            out = [m.result for _, m, c in replies[name]
                   if isinstance(m, ReplyCls) and c == "rdr"]
            replies[name].clear()
            return out

        def pump(seconds):
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                timer.service()
                for node in nodes.values():
                    node.prod()

        bls_keys = lp.pool_bls_keys(names)
        driver = SimReadDriver(submit, collect, pump, names, bls_keys,
                               freshness_s=1e9,
                               now=timer.get_current_time,
                               edge_names=[edge.name])
        served = 0
        writes = 0
        write_id = 1000
        t0 = time.perf_counter()
        for i in range(n_reads):
            if time.perf_counter() > t0 + timeout:
                break
            if i % write_every == write_every - 1:
                # the 5% write share: fire-and-forget, and the commit's
                # push fan-out invalidates the edge (anchor advance)
                user = Ed25519Signer(
                    seed=(b"edw%07d" % i).ljust(32, b"\0")[:32])
                w = Request(trustee.identifier, write_id,
                            {"type": NYM, "dest": user.identifier,
                             "verkey": user.verkey_b58})
                w.signature = trustee.sign_b58(w.signing_bytes())
                write_id += 1
                for n in names:
                    nodes[n].handle_client_message(w.to_dict(), "bench-w")
                writes += 1
                # let the write order: edge serving is synchronous (the
                # ladder never pumps on a cache hit), so the pool only
                # progresses when driven — and the commit's push
                # fan-out is what exercises invalidation + SWR
                pump(0.05)
            # CDN-shaped traffic: 90% of reads hammer 3 hot entries,
            # the tail rotates the cold set — hot entries amortize each
            # anchor-advance refill across many stale-while-revalidate
            # hits, the tail pays ~one refill per epoch per touched key
            hot = i % 10 < 9
            dest = users[i % 3] if hot else users[3 + i % 17]
            q = Request("reader", i + 1, {"type": GET_NYM,
                                          "dest": dest.identifier})
            if driver.read(q, per_node_s=2.0, step_s=0.001) is not None:
                served += 1
            for n in names:        # the push fan-out (anchor advances)
                route_pushes(n)
        dt = time.perf_counter() - t0
        s = driver.stats.summary()
        cs = edge.cache.stats
        # client-facing pool load (reads a VALIDATOR had to serve on the
        # ladder — the acceptance bar wants this ~0) vs CDN origin
        # refills (cold fills + revalidations: background traffic the
        # edge pays so clients don't)
        ladder_reads = s["single_reply_ok"] - s.get("edge_ok", 0)
        out = {"reads_served": served, "writes_submitted": writes,
               "reads_per_s": round(served / dt, 1) if dt else 0.0,
               "edge_served_rate": round(s.get("edge_ok", 0) / served, 4)
               if served else None,
               "edge_cache_hit_rate": round(cs["hits"] / cs["queries"], 4)
               if cs["queries"] else None,
               "edge_stale_served": cs["stale_served"],
               "edge_revalidations": cs["revalidations"],
               "edge_invalidations": cs["invalidations"],
               "pool_ladder_reads": ladder_reads,
               "origin_refills": cs["origin_fetches"],
               "origin_offload": round(
                   1.0 - cs["origin_fetches"] / cs["queries"], 4)
               if cs["queries"] else None,
               "bytes_per_edge_read": round(
                   cs["bytes_served"] / cs["hits"]) if cs["hits"] else None,
               "edge_verify_failures": s.get("edge_verify_failures", 0),
               "failovers": s["failovers"], "fallbacks": s["fallbacks"],
               "verify_ms_p50": s.get("verify_ms_p50"),
               "verify_ms_p95": s.get("verify_ms_p95"),
               "platform": "cpu"}
        return out
    except Exception as e:                       # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


def main():
    for name, fn in (("config1b", config1b_distinct_signers),
                     ("config2", config2_three_instances_mixed),
                     ("config3", config3_bls_proof_reads),
                     ("config4", config4_viewchange_under_load),
                     ("config5", config5_sim25),
                     ("config6", config6_read_plane),
                     ("config7", config7_ingress_10k),
                     ("config8", config8_pipeline_ab),
                     ("config10", config10_shards),
                     ("config11", config11_telemetry),
                     ("config12", config12_reshard),
                     ("config13", config13_commitment),
                     ("config16", config16_ordered_path),
                     ("config17", config17_federation),
                     ("config18", config18_autopilot),
                     ("config19", config19_edge)):
        print(name, json.dumps(fn()), flush=True)


if __name__ == "__main__":
    main()
