"""Launcher kind `tcp_service`: four start_node processes over localhost
TCP and one crypto service that owns the chip.

The launch logic follows plenum_tpu/tools/tcp_pool.py (keygen, genesis,
service first, prewarm [min_batch] x {64-key, full} then pin, nodes pinned
to the CPU). It does not call run_tcp_pool: every child is stopped with
reaper.stop_children, which waits again after kill(). This process is
launcher and client: it keeps JAX_PLATFORMS=cpu and makes no device query."""
from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import time

from benchmarks import reaper
from benchmarks.manifest import ROOT
from benchmarks.tcp_client import PoolConnections, ask
from benchmarks.topologies.base import Identities, require_native


class Launcher:
    def __init__(self, config: dict, run_dir: str, seed: int,
                 rehearse: bool):
        self.config, self.run_dir, self.seed = config, run_dir, seed
        self.rehearse = rehearse
        self.sizes = dict(config["sizes"], **(
            config["rehearsal_sizes"] if rehearse else {}))
        self.names = [f"Node{i + 1}" for i in range(config["nodes"])]
        self.f = (len(self.names) - 1) // 3
        self.procs: list = []
        self.service_proc = None
        self.loop = asyncio.new_event_loop()
        self.client = None
        self.metrics_folds = None
        self._ctl_req_id = 10 ** 9      # the trustee's own requests
        os.environ["JAX_PLATFORMS"] = "cpu"     # launcher + client only
        os.chdir(run_dir)       # unix socket paths stay short: relative
        self.sock = "crypto.sock"
        self.ctl = os.path.join(run_dir, "ctl")
        os.makedirs(self.ctl, exist_ok=True)
        from plenum_tpu.crypto.ed25519 import Ed25519Signer
        from benchmarks.traffic import did_seed
        self.trustee_seed = did_seed(seed, "trustee", 0)
        self.ids = Identities(seed, self.sizes["preload_dids"],
                              Ed25519Signer(seed=self.trustee_seed))

    # --- start --------------------------------------------------------------

    def _env(self) -> dict:
        env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
                   PLENUM_CRYPTO_SOCKET=self.sock)
        # the configuration's `settings` are what every node runs:
        # start_node lays them over Config() and refuses an unknown key
        env["PLENUM_CONFIG_JSON"] = json.dumps(self.config["settings"])
        return env

    def start(self, split) -> None:
        require_native()
        from plenum_tpu.tools import genesis, keygen
        from plenum_tpu.tools.tcp_pool import _free_ports
        ports = _free_ports(2 * len(self.names))
        self.specs = []
        for i, name in enumerate(self.names):
            keygen.save_keys(keygen.generate_keys(
                name, seed=(b"benchnode%d" % i).ljust(32, b"\0")),
                self.run_dir)
            self.specs.append((name, "127.0.0.1", ports[2 * i],
                               ports[2 * i + 1]))
        genesis.build_genesis_files(self.run_dir, self.specs,
                                    self.trustee_seed)
        self.genesis_domain = [json.loads(line) for line in open(
            os.path.join(self.run_dir, "domain_genesis.json"))]
        preload = self.ids.genesis_nyms(len(self.genesis_domain) + 1)
        with open(os.path.join(self.run_dir, "domain_genesis.json"),
                  "a") as fh:
            for txn in preload:
                fh.write(json.dumps(txn) + "\n")
        self.genesis_domain += preload
        split.mark("keys_genesis_preload")

        svc = dict(self.config["service"], **(
            self.config["service_rehearsal"] if self.rehearse else {}))
        self.on_device = svc["backend"].startswith("jax")
        env = self._env()
        svc_env = dict(env)
        if self.on_device:
            svc_env.pop("JAX_PLATFORMS", None)      # the chip's one owner
        self.service_log = os.path.join(self.run_dir, "crypto_service.log")
        with open(self.service_log, "wb") as log:
            self.service_proc = subprocess.Popen(
                [sys.executable, os.path.join(ROOT, "benchmarks",
                                              "service_entry.py"),
                 "--ctl", self.ctl, "--socket", self.sock,
                 "--backend", svc["backend"],
                 "--min-batch", str(svc["min_batch"])],
                env=svc_env, cwd=self.run_dir, stdout=log,
                stderr=subprocess.STDOUT)
        started = self._wait_line(self.service_log, self.service_proc,
                                  b'{"crypto_service"', 300.0)
        self.service_device = started.get("device")
        want = "tpu" if not self.rehearse else None
        if self.on_device and want and (
                not self.service_device
                or self.service_device["platform"] != want):
            raise SystemExit(f"benchmark: the crypto service found "
                             f"{self.service_device}, a TPU is required")
        split.mark("service_start")

        # nodes load the genesis (the preload) while the service warms
        for name in self.names:
            log = open(os.path.join(self.run_dir, f"{name}.out"), "wb")
            self.procs.append(subprocess.Popen(
                [sys.executable, "-m", "plenum_tpu.tools.start_node",
                 "--name", name, "--base-dir", self.run_dir,
                 "--kv", self.config["kv"], "--backend", "service"],
                env=env, cwd=self.run_dir, stdout=log,
                stderr=subprocess.STDOUT))
            log.close()
        t_nodes = time.perf_counter()

        from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
        ctl = FederatedEd25519Client(socket_path=self.sock)
        try:
            if self.on_device:
                ctl.prewarm(svc["prewarm_buckets"],
                            full_keys=svc["full_keys"])
            ctl.pin()
            self.at_pin = ctl.stats()
        finally:
            ctl.close()
        split.mark("service_prewarm_pin")
        split.parts["prewarm_compile"] = self.at_pin["compile"]

        for name, proc in zip(self.names, self.procs):
            self._wait_line(os.path.join(self.run_dir, f"{name}.out"), proc,
                            b'{"started"', 120.0)
        split.parts["node_start_overlapped"] = round(
            time.perf_counter() - t_nodes, 3)
        split.mark("node_start_wait")

        self.addrs = {s[0]: (s[1], s[3]) for s in self.specs}
        self.client = PoolConnections(self.addrs)
        self.loop.run_until_complete(self.client.connect())
        from plenum_tpu.parallel.crypto_service import ServiceEd25519Verifier
        self.plane = ServiceEd25519Verifier(socket_path=self.sock)
        split.mark("client_connect")

    @staticmethod
    def _wait_line(path: str, proc, prefix: bytes, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with open(path, "rb") as fh:
                for line in fh:
                    if line.startswith(prefix) and line.endswith(b"\n"):
                        return json.loads(line)
            if proc.poll() is not None:
                with open(path, "rb") as fh:
                    tail = fh.read()[-2000:].decode(errors="replace")
                raise RuntimeError(f"{path}: exited {proc.returncode} "
                                   f"before it started: {tail}")
            time.sleep(0.1)
        raise RuntimeError(f"{path}: no start line in {timeout:.0f} s")

    # --- traffic ------------------------------------------------------------

    def drive(self, requests, schedule, seconds, tracker, drain_s,
              actions=()) -> dict:
        return self.loop.run_until_complete(self.client.drive(
            requests, schedule, seconds, tracker, drain_s, actions))

    def snapshot(self) -> tuple[dict, list]:
        """-> (the served plane's counters, [its supervisor's stats]),
        read from the chip's owner in one round trip."""
        st = self.plane.stats()
        plane = st.get("plane") or {}
        return {"plane.items_received": st["items"],
                "plane.items_dispatched": st["dispatched_items"],
                "plane.cache_hits": st["cache_hits"],
                "plane.dispatches": st["dispatches"],
                "plane.errors": st.get("errors", 0),
                "plane.executables": st["compile"]["executables"],
                "plane.device_batches": plane.get("device_batches", 0),
                "plane.device_items": plane.get("device_items", 0)}, \
            [plane] if plane else []

    def must_stay_zero(self, before: dict, after: dict) -> dict:
        return {"executables obtained":
                after["plane.executables"] - before["plane.executables"],
                "service worker errors":
                after["plane.errors"] - before["plane.errors"]}

    # --- the chip's owner ---------------------------------------------------

    def _post(self, cmd: str, arg: str = "") -> str:
        path = os.path.join(self.ctl, cmd)
        with open(path + ".tmp", "w") as fh:
            fh.write(arg)
        os.replace(path + ".tmp", path)
        return path + ".done"

    @staticmethod
    def _answer(done: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while not os.path.exists(done):
            if time.monotonic() > deadline:
                raise RuntimeError(f"the service never wrote {done}")
            time.sleep(0.02)
        with open(done) as fh:
            body = json.load(fh)
        os.unlink(done)
        if "error" in body:
            raise RuntimeError(f"{os.path.basename(done)}: {body['error']}")
        return body

    def trace_start(self, log_dir: str, seconds: float) -> None:
        # posted, not awaited: the window's drive loop must not wait
        self._post("trace", f"{log_dir}\n{seconds}")

    def trace_wait(self) -> dict:
        """Block until the owner has written the trace out."""
        return self._answer(os.path.join(self.ctl, "trace.done"), 200.0)

    def device(self) -> dict:
        if not self.on_device:
            return {"platform": "cpu", "kind": "rehearsal", "count": 0,
                    "memory_peak_bytes": 0}
        return self._answer(self._post("report"), 60.0)

    def device_verdicts(self, items) -> list:
        return [bool(v) for v in self.plane.verify_batch(items)]

    # --- what the nodes hold ------------------------------------------------

    def _trustee_request(self, operation: dict):
        from plenum_tpu.common.request import Request
        self._ctl_req_id += 1
        req = Request(self.ids.trustee.identifier, self._ctl_req_id,
                      operation)
        req.signature = self.ids.trustee.sign_b58(req.signing_bytes())
        return req

    def node_states(self) -> list:
        """Every node's own account of its ledgers (VALIDATOR_INFO)."""
        from plenum_tpu.common.node_messages import (AUDIT_LEDGER_ID,
                                                     DOMAIN_LEDGER_ID)
        from plenum_tpu.execution.action_manager import VALIDATOR_INFO_ACTION

        async def all_nodes():
            return await asyncio.gather(*(ask(
                self.addrs[n], self._trustee_request(
                    {"type": VALIDATOR_INFO_ACTION})) for n in self.names))
        out = []
        for name, msg in zip(self.names,
                             self.loop.run_until_complete(all_nodes())):
            if msg.get("op") != "REPLY":
                raise RuntimeError(f"{name}: VALIDATOR_INFO gave {msg}")
            led = msg["result"]["data"]["ledgers"]
            led = {int(k): v for k, v in led.items()}
            dom, aud = led[DOMAIN_LEDGER_ID], led[AUDIT_LEDGER_ID]
            out.append({"node": name, "domain_size": dom["size"],
                        "domain_root": dom["root"],
                        "state_root": dom["state_root"],
                        "audit_root": aud["root"]})
        return out

    def _reader(self):
        from plenum_tpu.reads.client import VerifyingReadClient
        from plenum_tpu.tools.keygen import load_keys
        keys = {n: load_keys(self.run_dir, n)["bls_pk"] for n in self.names}
        # start_node's clock is time.perf_counter (CLOCK_MONOTONIC, one
        # per machine), so that is the clock a multi-signature's age is
        # judged on
        return VerifyingReadClient(self.addrs, self.f, keys,
                                   freshness_s=3600.0,
                                   now=time.perf_counter)

    def verified_reads(self, requests) -> list:
        """Each read to ONE node, accepted only when its proof and its
        n-f multi-signature verify client-side (reads/client.py).
        -> [(verified, data)]."""
        reader = self._reader()

        async def run():
            out = []
            for req in requests:
                before = reader.stats.single_reply_ok
                try:
                    msg = await reader.submit_read(req, timeout=20.0)
                except TimeoutError:
                    out.append((False, None))
                    continue
                ok = reader.stats.single_reply_ok == before + 1
                out.append((ok, (msg.get("result") or {}).get("data")))
            await reader.close()
            return out
        return self.loop.run_until_complete(run())

    def fetch_txns(self, seq_nos) -> dict:
        """Domain-ledger transactions by sequence number, from ONE node,
        each with its Merkle proof verified client-side (GET_TXN)."""
        from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID
        from plenum_tpu.common.request import Request
        from plenum_tpu.execution.txn import GET_TXN
        reader = self._reader()

        async def run():
            out = {}
            for n, seq in enumerate(seq_nos):
                msg = await reader.submit_read(Request(
                    "bench-reader", 2 * 10 ** 9 + n,
                    {"type": GET_TXN, "ledgerId": DOMAIN_LEDGER_ID,
                     "data": seq}), timeout=20.0)
                out[seq] = (msg.get("result") or {}).get("data")
            await reader.close()
            return out
        return self.loop.run_until_complete(run())

    # --- the end ------------------------------------------------------------

    def quiesce(self) -> None:
        """Stop the nodes (their SIGTERM handler flushes the metrics
        store) and read the first node's and every node's plane view."""
        if self.client is not None:
            self.loop.run_until_complete(self.client.close())
            self.client = None
        reaper.stop_children(self.procs)
        from plenum_tpu.tools.metrics_report import (derive_summary,
                                                     fold_rows, read_store)
        self.metrics_folds = [fold_rows(read_store(os.path.join(
            self.run_dir, n, "metrics"))) for n in self.names]
        self.node_plane = [derive_summary(f, 0.0)
                           for f in self.metrics_folds]

    def node_side_problems(self) -> list:
        """A node that gave up on the plane and verified on its own CPU
        shows in its own supervisor's counters."""
        out = []
        for name, summary in zip(self.names, self.node_plane or []):
            for k in ("crypto_fallback_batches", "crypto_hedge_wins",
                      "crypto_deadline_misses", "crypto_breaker_opens"):
                if summary.get(k):
                    out.append(f"{name}: {k} = {summary[k]}")
            state = summary.get("crypto_breaker_state", "closed")
            if state != "closed":
                out.append(f"{name}: breaker {state}")
        return out

    def samples(self) -> tuple[dict, dict]:
        """-> (samples by name in seconds, whole-run totals as counters)
        from the first node's flushed metrics store."""
        folds = self.metrics_folds[0]
        samples = {
            "commit.bls_verify_s":
                folds.get("commit_path.bls_verify_time", {}).get("samples", []),
            "commit.apply_s":
                folds.get("commit_path.apply_time", {}).get("samples", [])}
        batch = folds.get("node.ordered_batch_size", {})
        return samples, {"consensus.batches": batch.get("count", 0),
                         "consensus.batch_reqs": batch.get("sum", 0.0)}

    def stop(self) -> None:
        if self.client is not None:
            try:
                self.loop.run_until_complete(self.client.close())
            except Exception:
                pass
        plane = getattr(self, "plane", None)
        if plane is not None:
            plane.close()
        reaper.stop_children(self.procs + [self.service_proc])
        self.loop.close()
