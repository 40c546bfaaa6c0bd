"""Launcher kind `lane_per_chip`: four start_node processes over localhost
TCP, each owning one chip of a four-chip host and verifying for itself.

No crypto service: node i is started with `--backend jax` through
benchmarks/node_entry.py and sees chip i alone (`chip_env`: libtpu's own
variables, set here as an operator's unit file would set them). It builds
its single-lane ring, loads the pinned programs, pins, and only then prints
its start line. This process is launcher and client; it holds no device.

What it reads of the four planes comes over the client ports, from each
validator's VALIDATOR_INFO (`plane`: ring summary, supervisor, compile
counters, device). `snapshot()` gives cell.py FOUR supervisor records, so
its no-fallback rule holds every chip, and sums the counters under the names
the per-layer files read, with each owner's beside them. One comparison
belongs to this deployment, `verdicts_are_local` (see `local_shortfalls`).

Everything that is not about who owns the device is tcp_service's launcher,
inherited unchanged."""
from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys

from benchmarks.manifest import ROOT
from benchmarks.tcp_client import PoolConnections, ask
from benchmarks.topologies import tcp_service
from benchmarks.topologies.base import require_native

START_WAIT_S = 1000.0       # a cold machine compiles two programs first
REHEARSAL_DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 0,
                    "memory_peak_bytes": 0}


def chip_env(config: dict, i: int) -> dict:
    """Binds a process to chip i of its host: the configuration's
    `chip_binding` with i in place of `<i>` (PERF.md, "Step 0": found by
    probes/four_owners.py). Every such process then calls its chip
    device 0, so all four want the same executable-store keys."""
    return {key: value.replace("<i>", str(i))
            for key, value in config["chip_binding"].items()}


def plane_counters(names: list, infos: list) -> tuple[dict, list]:
    """Four VALIDATOR_INFO answers -> (counters: the planes' sums under
    the names the per-layer files read, each owner's beside them;
    one supervisor record per owner)."""
    from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID
    out, sups = {}, []
    for name, info in zip(names, infos):
        plane = info.get("plane")
        if not plane or not plane.get("supervisors"):
            raise SystemExit(f"benchmark: {name} reports no device plane of "
                             f"its own in VALIDATOR_INFO ({plane!r})")
        ring, sup = plane["ring"], plane["supervisors"][0]
        sups.append(dict(sup, label=name))
        ledgers = {int(k): v for k, v in info["ledgers"].items()}
        own = {"items_received": ring["verify_items"],
               "items_dispatched": ring["dispatched_items"],
               "cache_hits": ring["verdict_cache_hits"],
               "dispatches": ring["dispatches"],
               "unpinned_shapes": ring["unpinned_shapes"],
               "cmt_host_fallbacks": ring["cmt"]["host_fallbacks"],
               "executables": plane["compile"]["executables"],
               "device_batches": sup["device_batches"],
               "device_items": sup["device_items"],
               "ordered_writes": ledgers[DOMAIN_LEDGER_ID]["size"]}
        for key, value in own.items():
            out[f"plane.{name}.{key}"] = value
            out[f"plane.{key}"] = out.get(f"plane.{key}", 0) + value
        # a gauge, not summed: the share of this owner's waves that fit
        # the smallest pinned bucket (the rest took the next one)
        out[f"plane.{name}.bucket_hit_rate"] = ring["bucket_hit_rate"]
    # the pool orders each write once, whatever every validator spends on it
    out["consensus.ordered_writes"] = out[f"plane.{names[0]}.ordered_writes"]
    return out, sups


def ring_strayed(got: dict) -> dict:
    """What, in one validator's answer to `verdicts`, says that its ring
    did not take the sample to its device: nothing dispatched (the eight
    fresh and eight corrupted signatures are in no cache), no device
    batch, a shape outside the pinned ones, or a fallback of the
    supervisor. Empty when the answer stands."""
    from plenum_tpu.parallel.supervisor import fallback_growth
    ring, sup = got["ring"], got["supervisor"]
    out = dict(fallback_growth(sup["before"], sup["after"]))
    if ring["dispatched_items"] < 1 or ring["dispatches"] < 1:
        out["dispatched_items"] = ring["dispatched_items"]
    if ring["unpinned_shapes"]:
        out["unpinned_shapes"] = ring["unpinned_shapes"]
    if sup["after"]["device_batches"] <= sup["before"]["device_batches"]:
        out["device_batches"] = 0
    if sup["after"].get("breaker_state", "closed") != "closed":
        out["breaker_state"] = sup["after"]["breaker_state"]
    return out


def local_shortfalls(names: list, before: dict, after: dict) -> dict:
    """`verdicts_are_local`: over the window every validator dispatched to
    its own chip, or found in its own verdict cache, at least as many
    signatures as it ordered writes. A validator answered from another's
    verdict (a shared service, a shared cache) shows fewer.
    -> {validator: how many it is short}, empty when the guarantee held."""
    def grew(name, key):
        k = f"plane.{name}.{key}"
        return after[k] - before[k]
    short = {}
    for name in names:
        own = grew(name, "items_dispatched") + grew(name, "cache_hits")
        missing = grew(name, "ordered_writes") - own
        if missing > 0:
            short[name] = missing
    return short


class Launcher(tcp_service.Launcher):
    def __init__(self, config: dict, run_dir: str, seed: int,
                 rehearse: bool):
        super().__init__(config, run_dir, seed, rehearse)
        # every validator has a supervised plane of its own, in a
        # rehearsal too (a host double behind the same ring), so cell.py's
        # no-fallback rule and verdicts_are_local are judged there as well
        self.on_device = True
        self.ctls = [os.path.join(run_dir, f"ctl_{n}") for n in self.names]
        for ctl in self.ctls:
            os.makedirs(ctl, exist_ok=True)
        self.traced = False
        self.trace_cost = None
        self.devices: list = []

    # --- start --------------------------------------------------------------

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
        path = os.path.join(self.run_dir, "domain_genesis.json")
        self.genesis_domain = [json.loads(line) for line in open(path)]
        preload = self.ids.genesis_nyms(len(self.genesis_domain) + 1)
        with open(path, "a") as fh:
            for txn in preload:
                fh.write(json.dumps(txn) + "\n")
        self.genesis_domain += preload
        split.mark("keys_genesis_preload")

        # all four at once: what the store lacks, one of them compiles
        # and the others wait for and load (plenum_tpu/ops/aot.py)
        base = dict(os.environ, PYTHONPATH=ROOT,
                    PLENUM_CONFIG_JSON=json.dumps(self.config["settings"]))
        base.pop("PLENUM_CRYPTO_SOCKET", None)
        for i, (name, ctl) in enumerate(zip(self.names, self.ctls)):
            env = dict(base)
            if not self.rehearse:
                env.pop("JAX_PLATFORMS", None)      # this chip's one owner
                env.update(chip_env(self.config, i))
            log = open(os.path.join(self.run_dir, f"{name}.out"), "wb")
            self.procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(ROOT, "benchmarks", "node_entry.py"),
                 "--ctl", ctl, *(["--host-verifier"] if self.rehearse
                                 else []),
                 "--name", name, "--base-dir", self.run_dir,
                 "--kv", self.config["kv"], "--backend", "jax"],
                env=env, cwd=self.run_dir, stdout=log,
                stderr=subprocess.STDOUT))
            log.close()
        rings = []
        for name, proc in zip(self.names, self.procs):
            out = os.path.join(self.run_dir, f"{name}.out")
            self._wait_line(out, proc, b'{"started"', START_WAIT_S)
            rings.append(self._ring_line(name, out))
        split.mark("node_start_prewarm_pin")
        split.parts["prewarm_compile"] = {
            name: ring["compile"] for name, ring in zip(self.names, rings)}
        split.parts["ring_seconds"] = [ring["seconds"] for ring in rings]

        self.addrs = {s[0]: (s[1], s[3]) for s in self.specs}
        self.client = PoolConnections(self.addrs)
        self.loop.run_until_complete(self.client.connect())
        self.snapshot()         # no plane section -> out, inside set-up
        split.mark("client_connect")

    def _ring_line(self, name: str, out: str) -> dict:
        """The line a validator prints once its ring is pinned, BEFORE its
        start line. A program that serves without one (the parent of this
        deployment) is refused here, in seconds."""
        with open(out, "rb") as fh:
            lines = [ln for ln in fh if ln.startswith((b'{"ring"',
                                                       b'{"started"'))]
        if not lines or not lines[0].startswith(b'{"ring"'):
            raise SystemExit(f"benchmark: {name} served before it pinned a "
                             f"ring of its own: this program's start_node "
                             f"builds no device plane")
        ring = json.loads(lines[0])["ring"]
        device = ring.get("device") or {}
        if not ring.get("pinned") or (not self.rehearse and (
                device.get("platform") != "tpu" or device.get("count") != 1)):
            raise SystemExit(f"benchmark: {name} pinned {ring}; one TPU "
                             f"chip of its own is required")
        for key in ("shapes", "cmt_ladder"):
            if ring.get(key) != self.config[key]:
                raise SystemExit(
                    f"benchmark: {name} pinned {key} {ring.get(key)}, the "
                    f"configuration states {self.config[key]}")
        return ring

    # --- the four planes ----------------------------------------------------

    def _validator_infos(self) -> list:
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
            out.append(msg["result"]["data"])
        return out

    def snapshot(self) -> tuple[dict, list]:
        """-> (counters, one supervisor record per owner). Each asking is
        itself one trustee signature on each validator's ring."""
        return plane_counters(self.names, self._validator_infos())

    def must_stay_zero(self, before: dict, after: dict) -> dict:
        out = {label: after[key] - before[key] for label, key in (
            ("executables obtained", "plane.executables"),
            ("pipeline unpinned_shapes", "plane.unpinned_shapes"),
            ("cmt host_fallbacks", "plane.cmt_host_fallbacks"))}
        short = local_shortfalls(self.names, before, after)
        print(json.dumps({"compared": {
            "check": "plane.verdicts_are_local", "got": sum(short.values()),
            "limit": 0, "ok": not short, "note": json.dumps(short)
            if short else "signatures each validator dispatched or found "
            "in its own cache >= writes it ordered"}}), flush=True)
        for name, missing in short.items():
            out[f"verdicts_are_local: signatures {name} neither dispatched "
                f"nor cached itself, of the writes it ordered,"] = missing
        return out

    # --- the chips' owners --------------------------------------------------

    def _post_to(self, ctl: str, cmd: str, arg: str = "") -> str:
        path = os.path.join(ctl, cmd)
        with open(path + ".tmp", "w") as fh:
            fh.write(arg)
        os.replace(path + ".tmp", path)
        return path + ".done"

    def trace_start(self, log_dir: str, seconds: float) -> None:
        # held in the FIRST node's process; posted, not awaited
        self._post_to(self.ctls[0], "trace", f"{log_dir}\n{seconds}")
        self.traced = True

    def trace_wait(self) -> dict:
        return self.trace_cost      # quiesce() waited: the owner is gone

    def device(self) -> dict:
        if self.rehearse:
            return dict(REHEARSAL_DEVICE)
        first = self.devices[0]
        return {"platform": first["platform"], "kind": first["kind"],
                "count": sum(d["count"] for d in self.devices),
                "memory_peak_bytes": max(d["memory_peak_bytes"]
                                         for d in self.devices)}

    def device_verdicts(self, items) -> list:
        """The sample through EACH validator's own ring, the timed plane
        (node_entry.py `verdicts`). An item on which the four do not
        agree gives None, which equals no CPU verdict; so does every item
        of a validator whose ring did not answer from its device."""
        path = os.path.join(self.run_dir, "verdict_sample.json")
        with open(path, "w") as fh:
            json.dump([[part.hex() for part in item] for item in items], fh)
        waits = [self._post_to(ctl, "verdicts", path) for ctl in self.ctls]
        vectors = []
        for name, done in zip(self.names, waits):
            got = self._answer(done, 300.0)
            strayed = ring_strayed(got)
            if strayed:
                print(json.dumps({"verdict_sample_not_from_the_ring": name,
                                  "why": strayed, "ring": got["ring"],
                                  "supervisor": got["supervisor"]}),
                      flush=True)
                vectors.append([None] * len(items))
            else:
                vectors.append(got["verdicts"])
        return [col[0] if len(set(col)) == 1 else None
                for col in zip(*vectors)]

    # --- the end ------------------------------------------------------------

    def quiesce(self) -> None:
        """Before the owners go: the trace written out, each chip's
        report. Then tcp_service's: stop the nodes, read their stores."""
        if self.traced:
            self.trace_cost = self._answer(
                os.path.join(self.ctls[0], "trace.done"), 300.0)
        if not self.rehearse:
            waits = [self._post_to(ctl, "report") for ctl in self.ctls]
            self.devices = [self._answer(done, 60.0) for done in waits]
        super().quiesce()

    def samples(self) -> tuple[dict, dict]:
        samples, totals = super().samples()
        samples["ring.verdict_wait_s"] = self.metrics_folds[0].get(
            "pipeline.verdict_wait", {}).get("samples", [])
        return samples, totals
