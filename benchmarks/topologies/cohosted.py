"""Launcher kind `cohosted`: four nodes in ONE process behind the
in-process crypto ring (plenum_tpu.tools.local_pool.build_pool). This
process builds the pool and is the chip's owner.

`build_pool` is the benchmark's copy of local_pool.build_pool's
single-ring arm, with the configuration's `settings` laid over Config()
and the preload appended to the domain genesis. The drive is a copy of the
idea of local_pool.drive with a schedule and with f+1 matching replies
collected from ALL FOUR nodes' reply sinks, not from the first node's."""
from __future__ import annotations

import time

from benchmarks.accounting import Feeder
from benchmarks.topologies.base import (Identities, device_report,
                                        require_native)
from benchmarks.traffic import Op


NACKS = ("REQNACK", "REJECT", "LOAD_SHED")


def build_pool(names: list, backend: str, settings: dict, preload_of):
    """Four nodes in this process over the sim network, behind ONE crypto
    ring when the backend is `jax` (as local_pool.build_pool builds it:
    supervised JaxEd25519Verifier, the pipeline's own pinned ladder, SHA
    on the device). `preload_of(trustee, first_seq_no)` gives the NYM txns
    appended to the program's domain genesis.
    -> (local_pool.Pool, the domain genesis as the nodes loaded it)."""
    import time as _time

    from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID, Reply
    from plenum_tpu.common.timer import QueueTimer
    from plenum_tpu.config import load_config
    from plenum_tpu.network import SimNetwork, SimRandom
    from plenum_tpu.node import Node, NodeBootstrap
    from plenum_tpu.tools import local_pool as lp

    genesis, trustee = lp.build_genesis(names)
    domain = genesis[DOMAIN_LEDGER_ID]
    domain.extend(preload_of(trustee, len(domain) + 1))
    timer = QueueTimer(_time.perf_counter)
    net = SimNetwork(timer, SimRandom(1))
    net.set_latency(0.00005, 0.0002)
    config = load_config({"crypto_backend": backend,
                          "STATE_FRESHNESS_UPDATE_INTERVAL": 600.0},
                         settings)
    plane = pipeline = None
    if backend == "jax":
        from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
        from plenum_tpu.parallel.pipeline import CryptoPipeline
        from plenum_tpu.parallel.supervisor import supervise
        bucket = 1
        while bucket < len(names) * (config.LISTENER_MESSAGE_QUOTA
                                     + config.REMOTES_MESSAGE_QUOTA):
            bucket *= 2
        pipeline = CryptoPipeline(
            ed_inner=supervise(JaxEd25519Verifier(min_batch=1)),
            config=config.replace(PIPELINE_MAX_BUCKET=max(
                bucket, config.PIPELINE_MAX_BUCKET)),
            sha_device=True, sha_min_device=config.PIPELINE_SHA_MIN_BATCH)
        plane = pipeline.verifier()
    replies = {n: [] for n in names}
    nodes = {}
    for name in names:
        bus = net.create_peer(name)
        components = NodeBootstrap(
            name, genesis_txns=genesis, crypto_backend=backend,
            verifier=None, pipeline=pipeline,
            state_commitment=config.STATE_COMMITMENT,
            state_commitment_per_ledger=config.STATE_COMMITMENT_PER_LEDGER,
            verkle_width=config.VERKLE_WIDTH).build()
        nodes[name] = Node(
            name, timer, bus, components,
            client_send=lambda msg, client, n=name: replies[n].append(
                (_time.perf_counter(), msg, client)),
            config=config)
    net.connect_all()
    return lp.Pool(names, nodes, timer, trustee, replies, Reply,
                   DOMAIN_LEDGER_ID, plane, net), list(domain)


class Launcher:
    def __init__(self, config: dict, run_dir: str, seed: int,
                 rehearse: bool):
        self.config, self.run_dir, self.seed = config, run_dir, seed
        self.rehearse = rehearse
        self.sizes = dict(config["sizes"], **(
            config["rehearsal_sizes"] if rehearse else {}))
        self.backend = "cpu" if rehearse else config["backend"]
        self.on_device = self.backend.startswith("jax")
        self.names = [f"Node{i + 1}" for i in range(config["nodes"])]
        self.f = (len(self.names) - 1) // 3
        self.pool = None

    def start(self, split) -> None:
        require_native()
        if self.on_device:
            dev = device_report()       # this process now owns the chip
            if dev["platform"] != "tpu":
                raise SystemExit(f"benchmark: JAX found {dev}, a TPU is "
                                 f"required")
        split.mark("backend_init")
        from plenum_tpu.tools import local_pool as lp

        def preload_of(trustee, first_seq_no):
            self.ids = Identities(self.seed, self.sizes["preload_dids"],
                                  trustee)
            return self.ids.genesis_nyms(first_seq_no)

        self.pool, self.genesis_domain = build_pool(
            self.names, self.backend, self.config["settings"], preload_of)
        instances = {n._n_instances() for n in self.pool.nodes.values()}
        if instances != {self.config["protocol_instances"]}:
            raise SystemExit(f"benchmark: {instances} protocol instances, "
                             f"the configuration states "
                             f"{self.config['protocol_instances']}")
        split.mark("build_pool_preload")

        # warm_pool: prewarm pipe.buckets[:2] with the 64-key table, the
        # cmt ladder [1, 2, 4, 8], one txn end to end, then pin
        warm_req = self.ids.sign(
            [Op("NYM", -1, 0, "")], seed=-1, req_id_base=10 ** 8)[0]
        from plenum_tpu.ops import compile_stats
        c0 = compile_stats()
        warm = lp.warm_pool(self.pool, warm_req, timeout=900.0)
        c1 = compile_stats()
        self.at_pin = warm["supervisors"]
        split.mark("prewarm_pin")
        split.parts["prewarm_compile"] = {
            k: round(c1[k] - c0[k], 3) for k in c1}

    # --- traffic ------------------------------------------------------------

    def _collect(self, tracker) -> None:
        pool = self.pool
        for name in self.names:
            sink = pool.replies[name]
            if not sink:
                continue
            for ts, msg, _client in sink:
                if isinstance(msg, pool.Reply):
                    meta = msg.result.get("txn", {}).get("metadata", {})
                    tracker.on_reply((meta.get("from"), meta.get("reqId")),
                                     name, msg.result, ts)
                elif getattr(msg, "typename", "") in NACKS:
                    tracker.on_nack((getattr(msg, "identifier", None),
                                     msg.req_id), name,
                                    f"{type(msg).__name__}: "
                                    f"{getattr(msg, 'reason', '')}")
            sink.clear()

    def drive(self, requests, schedule, seconds, tracker, drain_s,
              actions=()) -> dict:
        """One window; see tcp_client.PoolConnections.drive."""
        pool, nodes = self.pool, self.pool.nodes
        actions = sorted(actions, key=lambda a: a[0])
        feeder = Feeder(requests, schedule, seconds, tracker,
                        time.perf_counter())
        a = 0
        while True:
            now = time.perf_counter()
            while a < len(actions) and now - feeder.t_open >= actions[a][0]:
                actions[a][1]()
                a += 1
            for request in feeder.take(now):
                wire = request.to_dict()
                for name in self.names:
                    nodes[name].handle_client_message(wire, "bench")
            if now >= feeder.t_close:
                break
            pool.prod_all()
            self._collect(tracker)
            if feeder.over(now):
                break
        t_drained = feeder.close(time.perf_counter(), drain_s)
        while tracker.open and time.perf_counter() < t_drained:
            pool.prod_all()
            self._collect(tracker)
        return feeder.times(t_drained, time.perf_counter())

    def snapshot(self) -> tuple[dict, list]:
        """-> (the ring's and the first node's counters, every lane's
        supervisor stats)."""
        from plenum_tpu.common.metrics import MetricsName
        from plenum_tpu.ops import compile_stats
        from plenum_tpu.tools.local_pool import plane_supervisors
        out = {"plane.executables": compile_stats()["executables"]}
        sups = [s.supervisor_stats()
                for s in plane_supervisors(self.pool.plane)]
        out["plane.device_batches"] = sum(s["device_batches"] for s in sups)
        out["plane.device_items"] = sum(s["device_items"] for s in sups)
        pipe = self.pool.pipeline
        if pipe is not None:
            st = pipe.stats
            other = st["bls_items"] + st["sha_items"] + st["cmt_items"]
            out["plane.items_received"] = st["submitted_items"] - other
            out["plane.items_dispatched"] = st["dispatched_items"]
            out["plane.dispatches"] = st["dispatches"]
            out["plane.unpinned_shapes"] = st["unpinned_shapes"]
            out["plane.cmt_host_fallbacks"] = st["cmt_host_fallbacks"]
        acc = self.pool.nodes[self.names[0]].metrics.accumulators.get(
            MetricsName.ORDERED_BATCH_SIZE)
        out["consensus.batches"] = acc.count if acc is not None else 0
        out["consensus.batch_reqs"] = acc.total if acc is not None else 0.0
        return out, sups

    def must_stay_zero(self, before: dict, after: dict) -> dict:
        return {label: after.get(key, 0) - before.get(key, 0)
                for label, key in (
                    ("executables obtained", "plane.executables"),
                    ("pipeline unpinned_shapes", "plane.unpinned_shapes"),
                    ("cmt host_fallbacks", "plane.cmt_host_fallbacks"))}

    # --- the chip's owner ---------------------------------------------------

    def trace_start(self, log_dir: str, seconds: float) -> None:
        """In a thread of this process (the chip's owner): the drive loop
        goes on while the trace is held and written out."""
        import threading

        from benchmarks.trace_reduce import hold_trace
        self._trace_cost: dict = {}
        self._trace_thread = threading.Thread(
            target=lambda: self._trace_cost.update(
                hold_trace(log_dir, seconds)), daemon=True)
        self._trace_thread.start()

    def trace_wait(self) -> dict:
        self._trace_thread.join(200.0)
        if not self._trace_cost:
            raise RuntimeError("the trace was not written out in 200 s")
        return self._trace_cost

    def device(self) -> dict:
        if not self.on_device:
            return {"platform": "cpu", "kind": "rehearsal", "count": 0,
                    "memory_peak_bytes": 0}
        return device_report()

    def device_verdicts(self, items) -> list:
        """Through the ring's own verifier face, as client-auth does."""
        plane = self.pool.plane
        if plane is None:               # rehearsal: the cpu backend
            from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
            plane = CpuEd25519Verifier()
        return [bool(v) for v in plane.verify_batch(items)]

    # --- what the nodes hold ------------------------------------------------

    def node_states(self) -> list:
        from plenum_tpu.common.node_messages import AUDIT_LEDGER_ID
        out = []
        for name in self.names:
            db = self.pool.nodes[name].c.db
            dom = db.get_ledger(self.pool.domain_ledger_id)
            out.append({
                "node": name, "domain_size": dom.size,
                "domain_root": dom.root_hash.hex(),
                "state_root": db.get_state(self.pool.domain_ledger_id)
                .committed_head_hash.hex(),
                "audit_root": db.get_ledger(AUDIT_LEDGER_ID)
                .root_hash.hex()})
        return out

    def fetch_txns(self, seq_nos) -> dict:
        ledger = self.pool.nodes[self.names[0]].c.db.get_ledger(
            self.pool.domain_ledger_id)
        return {s: ledger.get_by_seq_no(s) for s in seq_nos}

    def verified_reads(self, requests) -> list:
        """Each read to ONE node, accepted only when its proof and its
        n-f multi-signature verify client-side (reads/client.py)."""
        from plenum_tpu.reads import SimReadDriver
        from plenum_tpu.tools.local_pool import pool_bls_keys
        pool = self.pool

        def submit(name, req):
            pool.nodes[name].handle_client_message(req.to_dict(),
                                                   "bench-reader")

        def collect(name):
            out = [m.result for _, m, c in pool.replies[name]
                   if isinstance(m, pool.Reply) and c == "bench-reader"]
            pool.replies[name].clear()
            return out

        def pump(seconds):
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                pool.prod_all()

        driver = SimReadDriver(submit, collect, pump, self.names,
                               pool_bls_keys(self.names), freshness_s=1e9,
                               now=pool.timer.get_current_time)
        out = []
        for req in requests:
            before = driver.stats.single_reply_ok
            res = driver.read(req, per_node_s=5.0, step_s=0.001)
            out.append((driver.stats.single_reply_ok == before + 1,
                        (res or {}).get("data")))
        return out

    # --- the end ------------------------------------------------------------

    def quiesce(self) -> None:
        pass

    def node_side_problems(self) -> list:
        return []

    def samples(self) -> tuple[dict, dict]:
        from plenum_tpu.common.metrics import MetricsName
        acc = self.pool.nodes[self.names[0]].metrics.accumulators
        out = {}
        for key, name in ((MetricsName.COMMIT_BLS_VERIFY_TIME,
                           "commit.bls_verify_s"),
                          (MetricsName.COMMIT_APPLY_TIME, "commit.apply_s")):
            a = acc.get(key)
            out[name] = list(a.samples) if a is not None else []
        return out, {}

    def stop(self) -> None:
        close = getattr(getattr(self.pool, "pipeline", None), "close", None)
        if callable(close):
            close()                     # lane worker threads
