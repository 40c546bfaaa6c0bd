"""Run a real-time N-node pool in one process and measure write throughput.

This is the framework's equivalent of standing up the reference's 4-node local
pool under NYM load and reading the Monitor (BASELINE.md's prescription for
producing the north-star numbers). Nodes are real Node instances over
SimNetwork with microsecond latencies; time is REAL (QueueTimer over
perf_counter), so the printed TPS/latency are wall-clock measurements of the
full pipeline: client authN -> propagate quorum -> 3PC (with BLS signing and
order-time aggregate verification) -> execute -> REPLY.

Usage:  python -m plenum_tpu.tools.local_pool --nodes 4 --txns 200 \
            --backend cpu|jax [--json]
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from typing import NamedTuple, Optional, Sequence


def pool_bls_keys(names) -> dict:
    """node name -> BLS verkey under the name-seeded derivation every
    in-process genesis uses (build_genesis below, tests/test_pool.py).
    THE one copy: a verifying read client fed keys derived any other way
    would silently reject every proof and fall back to broadcast."""
    from plenum_tpu.crypto.bls import BlsCryptoSigner
    return {n: BlsCryptoSigner(seed=n.encode().ljust(32, b"\0")[:32]).pk
            for n in names}


def build_genesis(names, node_data_extra=None):
    """Pool + domain genesis txns for a named node set -> (genesis, trustee).

    node_data_extra: optional {name: dict} merged into each NODE txn's data
    (the TCP runner adds node_ip/node_port/client_ip/client_port here, the
    same fields the reference pool ledger carries)."""
    from plenum_tpu.common.node_messages import (DOMAIN_LEDGER_ID,
                                                 POOL_LEDGER_ID)
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution import txn as txn_lib
    from plenum_tpu.execution.txn import NODE, NYM, TRUSTEE

    trustee = Ed25519Signer(seed=b"local-pool-trustee".ljust(32, b"\0"))
    bls_keys = pool_bls_keys(names)
    pool_txns = []
    for i, name in enumerate(names):
        data = {"alias": name, "services": ["VALIDATOR"],
                "blskey": bls_keys[name]}
        if node_data_extra and name in node_data_extra:
            data.update(node_data_extra[name])
        txn = txn_lib.new_txn(NODE, {"dest": f"{name}Dest", "data": data})
        # genesis nodes are steward-owned by the trustee so owner-only
        # NODE edits (BLS key rotation, readdressing) are exercisable
        # against a genesis pool (churn soak, membership fuzz)
        txn["txn"].setdefault("metadata", {})["from"] = trustee.identifier
        txn_lib.set_seq_no(txn, i + 1)
        pool_txns.append(txn)
    nym = txn_lib.new_txn(NYM, {"dest": trustee.identifier,
                                "verkey": trustee.verkey_b58,
                                "role": TRUSTEE})
    txn_lib.set_seq_no(nym, 1)
    return {POOL_LEDGER_ID: pool_txns, DOMAIN_LEDGER_ID: [nym]}, trustee


class Pool(NamedTuple):
    """What `build_pool` hands back. Still the 9-tuple every caller
    unpacks; the names and the two helpers are for callers that drive
    more than one stage over the same live pool (run_load,
    chip_smoke.py)."""
    names: list
    nodes: dict
    timer: object
    trustee: object
    replies: dict
    Reply: type
    domain_ledger_id: int
    plane: object
    net: object

    @property
    def pipeline(self):
        """The shared CryptoPipeline ring, or None (cpu backend)."""
        return getattr(self.plane, "_pipeline", None)

    def prod_all(self) -> None:
        self.timer.service()
        for node in self.nodes.values():
            node.prod()
        if self.plane is not None:
            # every node has staged its cycle's signatures: one dispatch
            self.plane.flush()


BACKENDS = ("cpu", "jax")


def build_pool(n_nodes: int, backend: str, seed: int = 1,
               trace: bool = False, config_overrides: dict = None) -> Pool:
    if backend not in BACKENDS:
        # anything else would fall through make_verifier to the CPU
        # verifier and label the run with the string it was given
        raise ValueError(f"backend {backend!r}: build_pool builds "
                         f"{', '.join(BACKENDS)}")
    from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID, Reply
    from plenum_tpu.common.timer import QueueTimer
    from plenum_tpu.common.tracing import Tracer
    from plenum_tpu.config import Config
    from plenum_tpu.network import SimNetwork, SimRandom
    from plenum_tpu.node import Node, NodeBootstrap

    names = [f"Node{i + 1}" for i in range(n_nodes)]
    genesis, trustee = build_genesis(names)

    timer = QueueTimer(time.perf_counter)
    net = SimNetwork(timer, SimRandom(seed))
    net.set_latency(0.00005, 0.0002)       # LAN-ish, not the sim default 0.5s
    # 50ms partial-batch wait measured best here (fewer, fuller 3PC
    # batches amortize the per-batch BLS sign+aggregate-verify; p99
    # halves vs 5ms while p50 holds)
    config = Config(Max3PCBatchWait=0.05, crypto_backend=backend,
                    STATE_FRESHNESS_UPDATE_INTERVAL=600.0,
                    **(config_overrides or {}))
    replies: dict[str, list] = {n: [] for n in names}
    nodes = {}
    # co-hosted nodes share ONE crypto plane: the verify kernel is
    # serial-depth bound, so n_nodes small dispatches per cycle cost
    # ~n_nodes times one combined dispatch. On a device backend that
    # plane is the ring a validator that owns its chip builds
    # (tools/start_node.py), sized for n_nodes submitters: client-auth
    # Ed25519, BLS batch checks AND Merkle hashing coalesce/dedup across
    # the co-hosted nodes, supervised as in production (a device wedge
    # mid-run degrades the pool to CPU-speed verdicts and run_load
    # reports backend_state != "ok", never a healthy device run). The
    # PIPELINE_DEVICES / PIPELINE_REMOTE_HOSTS knobs select the
    # multi-chip and federated rings inside the same seam. cpu: None.
    from plenum_tpu.parallel.pipeline import make_crypto_pipeline
    pipeline = make_crypto_pipeline(config, backend, submitters=n_nodes)
    plane = pipeline.verifier() if pipeline is not None else None
    for name in names:
        bus = net.create_peer(name)
        components = NodeBootstrap(
            name, genesis_txns=genesis, crypto_backend=backend,
            pipeline=pipeline,
            state_commitment=config.STATE_COMMITMENT,
            state_commitment_per_ledger=config.STATE_COMMITMENT_PER_LEDGER,
            verkle_width=config.VERKLE_WIDTH).build()
        # traced runs carry real Tracers (shared in-process clock, so
        # assembly alignment is the identity); untraced runs keep the
        # NullTracer fast path and stay the honest TPS figures
        tracer = Tracer(name, timer.get_current_time,
                        clock_domain="shared") if trace else None
        nodes[name] = Node(
            name, timer, bus, components,
            client_send=lambda msg, client, n=name: replies[n].append(
                (time.perf_counter(), msg, client)),
            config=config, tracer=tracer)
    net.connect_all()
    return Pool(names, nodes, timer, trustee, replies, Reply,
                DOMAIN_LEDGER_ID, plane, net)


def commit_stage_stats(metrics) -> dict:
    """Post-ordering stage percentiles + pairing counters from an
    IN-PROCESS node's MetricsCollector (no flush required: the plain
    collector retains accumulators and their bounded raw samples).
    Keys match the bench line: bls_verify_ms/apply_ms/durable_ms/reply_ms
    p50+p95, pairings_per_batch, group_commit_batches."""
    from plenum_tpu.common.metrics import MetricsName, percentile
    acc = metrics.accumulators
    out = {}
    for key, label in ((MetricsName.COMMIT_BLS_VERIFY_TIME, "bls_verify_ms"),
                       (MetricsName.COMMIT_APPLY_TIME, "apply_ms"),
                       (MetricsName.COMMIT_WAVE_TIME, "commit_wave_ms"),
                       (MetricsName.COMMIT_DURABLE_TIME, "durable_ms"),
                       (MetricsName.COMMIT_REPLY_TIME, "reply_ms")):
        a = acc.get(key)
        if a is not None and a.samples:
            out[f"{label}_p50"] = round(percentile(a.samples, 0.5) * 1000, 3)
            out[f"{label}_p95"] = round(percentile(a.samples, 0.95) * 1000, 3)
    for key, label in ((MetricsName.BLS_PAIRINGS_PER_BATCH,
                        "pairings_per_batch"),
                       (MetricsName.GROUP_COMMIT_BATCHES,
                        "group_commit_batches")):
        a = acc.get(key)
        if a is not None and a.count:
            out[label] = round(a.total / a.count, 2)
    return out


def warm_pool(pool: Pool, warm_request, timeout: float) -> dict:
    """Untimed set-up, the pipeline warmup contract: compile the pad
    buckets steady state will dispatch WHILE THE CLOCK IS NOT RUNNING
    (every lane of a multi-device ring at once), run one txn end-to-end
    (fills the per-verkey point caches), then pin. After pin() the ring
    only selects compiled shapes (pad up / split), so the timed phase can
    never stall on a mid-run XLA compile: before prewarm+pin, one cold
    128-bucket wave cost a 25 s retrace+compile mid-measurement and
    collapsed this pool from 206 to 5.7 TPS. A prewarm the device did not
    answer raises (parallel/pipeline._warm_dispatch).
    -> {"setup_s", "supervisors": per-lane supervisor_stats() at pin}."""
    t0 = time.perf_counter()
    names, replies = pool.names, pool.replies
    pipe = pool.pipeline
    if pipe is not None:
        from plenum_tpu.parallel.pipeline import CMT_LADDER
        pipe.prewarm(pipe.buckets[:2])
        pipe.prewarm_cmt(CMT_LADDER)
    for n in names:
        pool.nodes[n].handle_client_message(warm_request.to_dict(), "warmup")
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        pool.prod_all()
        if any(isinstance(m, pool.Reply) for _, m, _ in replies[names[0]]):
            break
    else:
        raise RuntimeError(f"warm-up txn got no reply in {timeout:.0f}s")
    for n in names:
        replies[n].clear()
    if pipe is not None:
        pipe.pin()
    return {"setup_s": round(time.perf_counter() - t0, 3),
            "supervisors": [sup.supervisor_stats()
                            for sup in plane_supervisors(pool.plane)]}


def drive(pool: Pool, requests: Sequence, window: int = 256,
          timeout: float = 120.0) -> tuple[dict, dict, float]:
    """Feed pre-signed requests to every node with at most `window` in
    flight until each has its REPLY from the first node (or `timeout`).
    -> (first_reply {digest: t}, submit_times {digest: t}, seconds).
    256 floods the pipeline; small windows trickle per-tick batches."""
    names, nodes = pool.names, pool.nodes
    sink = pool.replies[names[0]]
    submit_times: dict[str, float] = {}
    first_reply: dict[str, float] = {}
    next_submit = 0
    t_start = time.perf_counter()
    deadline = t_start + timeout
    while len(first_reply) < len(requests) \
            and time.perf_counter() < deadline:
        # feed in chunks so the propagate pipeline stays busy but inboxes
        # don't balloon
        while next_submit < len(requests) \
                and next_submit - len(first_reply) < window:
            req = requests[next_submit]
            submit_times[req.digest] = time.perf_counter()
            for n in names:
                nodes[n].handle_client_message(req.to_dict(), "bench")
            next_submit += 1
        pool.prod_all()
        for ts, msg, _client in sink:
            if isinstance(msg, pool.Reply):
                digest = msg.result.get("txn", {}).get("metadata", {}) \
                    .get("digest")
                if digest in submit_times and digest not in first_reply:
                    first_reply[digest] = ts
        sink.clear()
    return first_reply, submit_times, time.perf_counter() - t_start


def pool_roots(pool: Pool) -> dict:
    """Every node's committed domain-ledger, domain-state and audit-ledger
    roots (hex) + whether all nodes agree on all three. Sizes agreeing
    says the nodes ordered equally MANY txns; roots agreeing says they
    ordered and applied the SAME ones."""
    from plenum_tpu.common.node_messages import AUDIT_LEDGER_ID
    per_node = {}
    for n in pool.names:
        db = pool.nodes[n].c.db
        per_node[n] = {
            "domain_ledger": db.get_ledger(pool.domain_ledger_id)
            .root_hash.hex(),
            "domain_state": db.get_state(pool.domain_ledger_id)
            .committed_head_hash.hex(),
            "audit_ledger": db.get_ledger(AUDIT_LEDGER_ID).root_hash.hex(),
        }
    first = per_node[pool.names[0]]
    agree = all(r == first for r in per_node.values())
    return {"agree": agree, **first,
            **({} if agree else {"per_node": per_node})}


def plane_supervisors(plane) -> list:
    """Every SupervisedVerifier behind a pool's crypto plane (a ring's
    verifier view, or None): one per chip lane for a multi-device ring,
    the single ring's otherwise."""
    pipe = getattr(plane, "_pipeline", None)
    return pipe.supervisors() if pipe is not None else []


def plane_report(plane, at_pin: Optional[list] = None) -> dict:
    """-> {"crypto_plane": counters, "backend_state": ok|fallback|open}
    for a device-backed plane, {} otherwise. Counters sum over lanes;
    the breaker state is the worst lane's. backend_state is "ok" only
    when every breaker is closed AND nothing was hedged or fell back
    since `at_pin` (the per-lane supervisor_stats() warm_pool returned):
    a run the CPU quietly finished must not read as a device run."""
    from plenum_tpu.parallel.supervisor import (FALLBACK_COUNTERS, STATE_CODE,
                                                fallback_growth)
    stats = [sup.supervisor_stats() for sup in plane_supervisors(plane)]
    if not stats:
        return {}
    keys = FALLBACK_COUNTERS + ("device_batches", "device_items")
    counters = {k: sum(st[k] for st in stats) for k in keys}
    worst = max((st["breaker_state"] for st in stats),
                key=STATE_CODE.__getitem__)
    counters["breaker_state"] = worst
    since_pin: dict = {}
    for before, after in zip(at_pin or [], stats):
        for k, d in fallback_growth(before, after).items():
            since_pin[k] = since_pin.get(k, 0) + d
    if at_pin is not None:
        counters["fallbacks_since_pin"] = since_pin
        counters["device_batches_since_pin"] = sum(
            a["device_batches"] - b["device_batches"]
            for b, a in zip(at_pin, stats))
    state = {"closed": "ok", "half_open": "fallback", "open": "open"}[worst]
    if state == "ok" and since_pin:
        state = "fallback"
    return {"crypto_plane": counters, "backend_state": state}


def signed_nyms(trustee, n: int, tag: bytes = b"lpu", first_req_id: int = 1):
    """n trustee-signed NYM writes creating n seed-derived DIDs
    -> (requests, user signers)."""
    from plenum_tpu.common.request import Request
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    from plenum_tpu.execution.txn import NYM
    users, requests = [], []
    for i in range(n):
        user = Ed25519Signer(seed=(tag + b"%d" % i).ljust(32, b"\0")[:32])
        req = Request(trustee.identifier, first_req_id + i,
                      {"type": NYM, "dest": user.identifier,
                       "verkey": user.verkey_b58})
        req.signature = trustee.sign_b58(req.signing_bytes())
        users.append(user)
        requests.append(req)
    return requests, users


def run_load(n_nodes: int = 4, n_txns: int = 200, backend: str = "cpu",
             timeout: float = 120.0, trace: bool = False,
             config_overrides: dict = None, window: int = 256) -> dict:
    """window: max requests in flight while feeding (see `drive`)."""
    pool = build_pool(n_nodes, backend, trace=trace,
                      config_overrides=config_overrides)
    names, nodes = pool.names, pool.nodes

    # pre-sign the whole workload so client-side signing isn't measured
    requests, _users = signed_nyms(pool.trustee, n_txns)
    warm = warm_pool(pool, requests.pop(), timeout)

    from plenum_tpu.ops import compile_stats
    compiled_before = compile_stats()["executables"]
    n_txns = len(requests)
    first_reply, submit_times, t_total = drive(pool, requests, window,
                                               timeout)
    done = len(first_reply)
    window_executables = compile_stats()["executables"] - compiled_before

    latencies = sorted(first_reply[d] - submit_times[d]
                       for d in first_reply if d in submit_times)
    sizes = {nodes[n].c.db.get_ledger(pool.domain_ledger_id).size
             for n in names}
    roots = pool_roots(pool)
    stage = commit_stage_stats(nodes[names[0]].metrics)
    trace_summary = None
    if trace:
        # assemble the per-node rings into the bench line's waterfall
        # summary, and check stage sums against the MEASURED client e2e
        # latency (submit -> first REPLY) — both ride one process clock
        from plenum_tpu.common.metrics import percentile
        from plenum_tpu.tools.trace_report import assemble, summarize
        report = assemble([nodes[n].tracer.snapshot() for n in names])
        trace_summary = summarize(report)
        ratios = []
        for digest, per_node in report["requests"].items():
            e2e = (first_reply.get(digest, 0.0)
                   - submit_times.get(digest, 0.0))
            wf = per_node.get(names[0])
            if wf is not None and e2e > 0:
                ratios.append(wf["total"] / e2e)
        if ratios:
            trace_summary["stage_sum_vs_e2e_p50"] = round(
                percentile(ratios, 0.5), 4)
    pipe = pool.pipeline
    pipeline_summary = pipe.summary() if pipe is not None else None
    # controller trajectory from the master PRIMARY (Node1 under the
    # round-robin selector): final knob positions + the rolling per-stage
    # p50/p95 vs the SLO that put them there — the bench line's view of
    # the closed loop
    ctl = getattr(nodes[names[0]], "batch_controller", None)
    return {
        **({"trace": trace_summary} if trace_summary else {}),
        **({"pipeline": pipeline_summary} if pipeline_summary else {}),
        **({"controller": ctl.trajectory()} if ctl is not None else {}),
        **({"commit_stage": stage} if stage else {}),
        **plane_report(pool.plane, at_pin=warm["supervisors"]),
        "backend": backend,
        "nodes": n_nodes,
        "txns_ordered": done,
        "txns_requested": n_txns,
        "setup_s": warm["setup_s"],
        "window_executables": window_executables,
        "seconds": round(t_total, 3),
        "tps": round(done / t_total, 1) if t_total > 0 else 0.0,
        "p50_latency_ms": round(
            statistics.median(latencies) * 1000, 1) if latencies else None,
        "p99_latency_ms": round(
            latencies[int(len(latencies) * 0.99)] * 1000, 1)
        if latencies else None,
        "ledger_sizes_agree": len(sizes) == 1,
        "roots": roots,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--txns", type=int, default=200)
    ap.add_argument("--backend", default="cpu", choices=BACKENDS)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    stats = run_load(args.nodes, args.txns, args.backend)
    if args.json:
        print(json.dumps(stats))
    else:
        print(f"{stats['txns_ordered']}/{stats['txns_requested']} txns in "
              f"{stats['seconds']}s -> {stats['tps']} TPS "
              f"(p50 {stats['p50_latency_ms']} ms, "
              f"p99 {stats['p99_latency_ms']} ms, backend={stats['backend']})")


if __name__ == "__main__":
    main()
