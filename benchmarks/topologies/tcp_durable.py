"""Launcher kind `tcp_durable`: tcp_service's pool on its disks, crashed
and started again after every window.

Everything that is not about the store, the kill and the restart is
tcp_service's launcher, inherited unchanged: the same four `start_node
--backend service` processes, the same crypto service, client and
accounting. The window is plain steady traffic; no fault falls inside it.
What this kind adds happens at the first `node_states()` after the windows,
the seam cell.py's comparison opens with:

  a. the windows' samples are put on record: wait for each validator's
     next periodic metrics flush (a SIGTERM would flush too, and close the
     stores cleanly, which is no crash);
  b. a tail of further writes (signed at set-up) is driven closed loop and
     SIGKILL goes to all four validators at the instant a given number of
     them has its f+1-th matching REPLY: mid-commit, writes in flight;
  c. before any restart, benchmarks/reference_store.py (nothing of the
     program) reads each validator's domain txn log file: every write
     acknowledged by then is on at least f+1 of the four disks;
  d. the four are started again on their directories and ports, each with a
     deadline, the client reconnects; each start line says what was
     recovered, and the engine it names is checked in both lives;
  e. cell.py's own comparisons then run, unchanged, against the restarted
     pool (no write has been ordered since the restart: the read-backs are
     served from the recovered state and BLS store); the launcher adds that
     every tail write acknowledged before the kill is in the ledger;
  f. in `quiesce()`, after the comparisons: fresh writes are acknowledged
     on f+1 by the restarted pool within a deadline.

Its findings reach `correct` through `node_side_problems()` (the seam
`nodes.fallback_problems` reads), each printed as a `compared` line of its
own beside its limit, as `plane.verdicts_are_local` is. In a rehearsal the
served plane has no device, so cell.py skips that seam unless `on_device`
is set: this kind sets it once the windows are over. Every wait here has a
deadline."""
from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

from benchmarks import accounting, manifest, reference_store, traffic
from benchmarks.tcp_client import PoolConnections
from benchmarks.topologies import tcp_service
from benchmarks.topologies.lane_per_chip import REHEARSAL_DEVICE

FLUSH_WAIT_S = 15.0         # the nodes flush their metrics every 10 s
AGREE_WAIT_S = 20.0


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def compared(check: str, got, limit, ok: bool, note: str = "") -> None:
    say(compared={"check": check, "got": got, "limit": limit, "ok": bool(ok),
                  **({"note": note} if note else {})})


def fs_type(path: str) -> str:
    """The filesystem `path` lies on, by the longest mount point over it."""
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as fh:
            for line in fh:
                _, mount, fstype = line.split()[:3]
                if (path == mount or path.startswith(mount.rstrip("/") + "/")) \
                        and len(mount) > len(best):
                    best, kind = mount, fstype
    except OSError:
        pass
    return kind


class KillAt(accounting.Tracker):
    """A Tracker that calls `then` at the instant the n-th request gets
    its quorum-th matching reply, inside the reader that saw it."""

    def __init__(self, quorum: int, n: int, then):
        super().__init__(quorum)
        self._n, self._then = n, then

    def on_reply(self, key, node, result, now) -> bool:
        done = super().on_reply(key, node, result, now)
        if done and len(self.acked) == self._n:
            self._then()
        return done


class Launcher(tcp_service.Launcher):
    def __init__(self, config: dict, run_dir: str, seed: int,
                 rehearse: bool):
        super().__init__(config, run_dir, seed, rehearse)
        self.crash = dict(config["crash"], **(
            config["crash_rehearsal"] if rehearse else {}))
        self.trackers: list = []        # every drive's, for comparison c
        self.drive_ended = time.time()
        self.restarted = False
        self.first_life = None          # metrics folds at the crash
        self.problems: list[str] = []
        self.engines: dict = {}

    # --- start --------------------------------------------------------------

    def start(self, split) -> None:
        super().start(split)
        self._check_engines("first")
        say(durable_setup={
            "kv": self.config["kv"], "engines": self.engines["first"],
            "run_dir_filesystem": fs_type(self.run_dir),
            "data_dirs": [os.path.join(n, "data") for n in self.names]})
        mix = manifest._load(manifest.HERE, "traffic",
                             self.crash["traffic"] + ".json")
        self.tail = self._signed(mix, self.seed ^ 0x7A11, 5 * 10 ** 8,
                                 self.crash["tail_writes"])
        self.fresh = self._signed(mix, self.seed ^ 0xF4E5, 6 * 10 ** 8,
                                  self.crash["liveness_writes"])
        split.mark("sign_crash_tail")

    def _signed(self, mix: dict, seed: int, req_id_base: int, n: int) -> list:
        ops = traffic.plan(mix, seed, n, self.sizes["preload_dids"])
        return self.ids.sign(ops, seed, req_id_base)

    def _check_engines(self, life: str, suffix: str = ".out") -> list:
        """The engine each validator says it runs, against the one the
        configuration states. A program whose start line names none (the
        parent of this deployment) is refused here, in seconds."""
        lines = [self._wait_line(os.path.join(self.run_dir, n + suffix), p,
                                 b'{"started"', 1.0)
                 for n, p in zip(self.names, self.procs)]
        self.engines[life] = {n: line.get("engine")
                              for n, line in zip(self.names, lines)}
        wrong = {n: e for n, e in self.engines[life].items()
                 if e != self.config["kv_engine"]}
        if wrong:
            raise SystemExit(
                f"benchmark: the configuration states the {self.config['kv_engine']!r} "
                f"engine; validators report {wrong} ({life} life)")
        return lines

    # --- traffic ------------------------------------------------------------

    def drive(self, requests, schedule, seconds, tracker, drain_s,
              actions=()) -> dict:
        self.trackers.append(tracker)
        try:
            return super().drive(requests, schedule, seconds, tracker,
                                 drain_s, actions)
        finally:
            self.drive_ended = time.time()

    def snapshot(self) -> tuple[dict, list]:
        """tcp_service's, and the first validator's own storage counters
        (VALIDATOR_INFO `storage`; a program without them leaves the
        names out, and the readers then report nothing)."""
        counters, sups = super().snapshot()
        info = self._validator_info(self.names[0])
        storage = info.get("storage")
        if storage:
            from plenum_tpu.common.node_messages import DOMAIN_LEDGER_ID
            ledgers = {int(k): v for k, v in info["ledgers"].items()}
            counters.update({
                "storage.rows_written": storage["rows"],
                "storage.bytes_written": storage["bytes"],
                "storage.flushes": storage["flushes"],
                "storage.file_gets": storage["gets"],
                "storage.domain_txns": ledgers[DOMAIN_LEDGER_ID]["size"]})
        return counters, sups

    def _validator_info(self, name: str) -> dict:
        from benchmarks.tcp_client import ask
        from plenum_tpu.execution.action_manager import VALIDATOR_INFO_ACTION
        msg = self.loop.run_until_complete(ask(
            self.addrs[name], self._trustee_request(
                {"type": VALIDATOR_INFO_ACTION})))
        if msg.get("op") != "REPLY":
            raise RuntimeError(f"{name}: VALIDATOR_INFO gave {msg}")
        return msg["result"]["data"]

    async def _closed_loop(self, requests, in_flight: int, tracker,
                           deadline_s: float, stop) -> None:
        """Closed loop until every request is answered, `stop()` says so,
        or the deadline passes."""
        client = self.client
        client.tracker = tracker
        feeder = accounting.Feeder(requests, {"in_flight": in_flight}, None,
                                   tracker, time.perf_counter())
        deadline = time.monotonic() + deadline_s
        while not stop() and time.monotonic() < deadline:
            try:
                batch = feeder.take(time.perf_counter())
                for request in batch:
                    client.write(request)
                if batch:
                    await client.flush()
            except (OSError, asyncio.TimeoutError):
                break                   # the pool went away under the write
            if feeder.over(time.perf_counter()):
                break
            client.progress.clear()
            try:
                await asyncio.wait_for(client.progress.wait(), 0.005)
            except asyncio.TimeoutError:
                pass

    # --- the crash and the restart -------------------------------------------

    def node_states(self) -> list:
        if not self.restarted:
            self._crash_and_restart()
        return super().node_states()

    def _record_first_life(self) -> None:
        """a. Wait until every validator has flushed its metrics past the
        last drive, then keep what its store holds."""
        from plenum_tpu.tools.metrics_report import fold_rows, read_store
        paths = [os.path.join(self.run_dir, n, "metrics") for n in self.names]
        deadline = time.monotonic() + FLUSH_WAIT_S
        while True:
            rows = [read_store(p) for p in paths]
            if all(r and r[-1][0] >= self.drive_ended for r in rows) \
                    or time.monotonic() > deadline:
                break
            time.sleep(0.25)
        self.first_life = [fold_rows(r) for r in rows]

    def _crash_and_restart(self) -> None:
        crash, quorum = self.crash, self.f + 1
        self._record_first_life()

        # b. the crash under load
        killed = []

        def kill_all():
            for p in self.procs:
                os.kill(p.pid, signal.SIGKILL)
            killed.append(time.time())
        tail = KillAt(quorum, crash["kill_after_acks"], kill_all)
        self.trackers.append(tail)
        self.loop.run_until_complete(self._closed_loop(
            self.tail, crash["in_flight"], tail, 60.0, lambda: bool(killed)))
        if not killed:
            raise RuntimeError(
                f"crash tail: {len(tail.acked)} of {crash['kill_after_acks']} "
                f"writes acknowledged in 60 s; no kill was sent")
        acked_tail = dict(tail.results)     # frozen at the kill
        self.loop.run_until_complete(self.client.close())
        self.client = None
        for p in self.procs:
            p.wait(timeout=30.0)
        say(crash={"killed": len(self.procs), "signal": "SIGKILL",
                   "tail_sent": len(tail.sent),
                   "tail_acknowledged_at_kill": len(acked_tail),
                   "tail_in_flight_at_kill": tail.open})

        # c. on the disks, before any restart
        acknowledged = {}
        for tracker in self.trackers:
            results = acked_tail if tracker is tail else tracker.results
            for key, result in results.items():
                acknowledged[key] = (result.get("txnMetadata") or {}).get(
                    "seqNo")
        logs = [reference_store.ledger_txns(os.path.join(
            self.run_dir, n, "data", "domain_log")) for n in self.names]
        held = reference_store.disks_holding(
            acknowledged, [reference_store.requests_of(log) for log in logs])
        short = sorted(k for k, n in held.items() if n < quorum)
        say(on_disk={"acknowledged_writes": len(acknowledged),
                     "domain_txns_on_each_disk": [len(log) for log in logs],
                     "on_fewest_disks": min(held.values(), default=0)})
        compared(f"durable.acknowledged_on_fewer_than_{quorum}_disks",
                 len(short), 0, not short and bool(acknowledged),
                 json.dumps(short[:5]) if short else "")
        if short or not acknowledged:
            self.problems.append(
                f"durability: {len(short)} of {len(acknowledged)} "
                f"acknowledged writes on fewer than {quorum} disks")

        # d. the restart
        self.before_restart()
        t0 = time.perf_counter()
        self.procs, env = [], self._env()
        for name in self.names:
            with open(os.path.join(self.run_dir, f"{name}.life2.out"),
                      "wb") as log:
                self.procs.append(subprocess.Popen(
                    [sys.executable, "-m", "plenum_tpu.tools.start_node",
                     "--name", name, "--base-dir", self.run_dir,
                     "--kv", self.config["kv"], "--backend", "service"],
                    env=env, cwd=self.run_dir, stdout=log,
                    stderr=subprocess.STDOUT))
        deadline = time.monotonic() + crash["restart_deadline_s"]
        for name, proc in zip(self.names, self.procs):
            self._wait_line(os.path.join(self.run_dir, f"{name}.life2.out"),
                            proc, b'{"started"',
                            max(1.0, deadline - time.monotonic()))
        restart_s = time.perf_counter() - t0
        self.restarted = True
        for name, line in zip(self.names,
                              self._check_engines("second", ".life2.out")):
            say(restarted=name, engine=line.get("engine"),
                recovery=line.get("recovery"))
        compared("durable.restart_s", round(restart_s, 3),
                 crash["restart_deadline_s"],
                 restart_s <= crash["restart_deadline_s"])
        self.client = PoolConnections(self.addrs)
        self.loop.run_until_complete(self.client.connect())

        # e. (the launcher's part) every tail write acknowledged before
        # the kill is in the ledger the four now agree on
        deadline = time.monotonic() + AGREE_WAIT_S
        while True:
            states = super().node_states()
            if len({(s["domain_size"], s["domain_root"]) for s in states}) \
                    == 1 or time.monotonic() > deadline:
                break
            time.sleep(0.2)
        want = {(r.get("txnMetadata") or {}).get("seqNo"): key
                for key, r in acked_tail.items()}
        fetched = self.fetch_txns(sorted(want))
        missing = 0
        for seq, key in want.items():
            meta = ((fetched.get(seq) or {}).get("txn") or {}).get(
                "metadata") or {}
            missing += (meta.get("from"), meta.get("reqId")) != key
        compared("durable.tail_acknowledged_missing_after_restart", missing,
                 0, missing == 0, f"of {len(want)} acknowledged before the "
                 f"kill; ledger size {states[0]['domain_size']}")
        if missing:
            self.problems.append(f"durability: {missing} tail writes "
                                 f"acknowledged before the kill are not in "
                                 f"the restarted pool's ledger")
        # the findings above reach `correct` through node_side_problems,
        # in a rehearsal too (module docstring)
        self.on_device = True

    def before_restart(self) -> None:
        """Between the on-disk comparison and the restart: nothing. The
        tests' seam for what a disk can lose while its validator is down."""

    # --- the end ------------------------------------------------------------

    def device(self) -> dict:
        if self.rehearse:           # on_device is set late (see above)
            return dict(REHEARSAL_DEVICE)
        return super().device()

    def quiesce(self) -> None:
        """f. liveness, after the comparisons; then tcp_service's."""
        if self.restarted:
            crash, tracker = self.crash, accounting.Tracker(self.f + 1)
            t0 = time.perf_counter()
            self.loop.run_until_complete(self._closed_loop(
                self.fresh, 32, tracker, crash["liveness_deadline_s"],
                lambda: False))
            n = len(tracker.acked)
            compared("durable.post_restart_writes_acknowledged", n,
                     len(self.fresh), n == len(self.fresh),
                     f"in {time.perf_counter() - t0:.2f} s of "
                     f"{crash['liveness_deadline_s']} s; nacks "
                     f"{list(tracker.nacked.values())[:2]}")
            if n != len(self.fresh):
                self.problems.append(
                    f"liveness: the restarted pool acknowledged {n} of "
                    f"{len(self.fresh)} fresh writes")
        super().quiesce()

    def node_side_problems(self) -> list:
        """Both lives of every validator: the metrics store a restarted
        node appends to is the one its first life wrote, so the base's
        reading covers both. In a rehearsal the nodes have no device
        plane to judge; the durability findings stand alone."""
        base = [] if self.rehearse else super().node_side_problems()
        return base + self.problems

    def samples(self) -> tuple[dict, dict]:
        """From the record taken before the crash: the window's samples,
        with nothing of the tail, the restart or the liveness writes."""
        after, self.metrics_folds = self.metrics_folds, \
            self.first_life or self.metrics_folds
        try:
            samples, totals = super().samples()
        finally:
            self.metrics_folds = after
        samples["storage.flush_s"] = (self.first_life or after)[0].get(
            "storage.flush_time", {}).get("samples", [])
        return samples, totals
