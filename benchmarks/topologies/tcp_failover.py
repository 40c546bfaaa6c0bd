"""Launcher kind `tcp_failover`: tcp_durable's pool with its master primary
SIGKILLed inside the timed window.

Start, service, stores, client accounting, the reading of the disks and the
liveness writes are tcp_durable's and tcp_service's launchers, inherited.
What this kind adds:

  a. before the window opens every validator's VALIDATOR_INFO names the
     view and its master primary: that validator is the victim;
  b. the kill is a timed action of `drive`, beside the ones cell.py hands
     in: SIGKILL to the victim's process when the drive's clock reaches the
     configuration's share of the window. The client (SurvivingConnections)
     drops the connection its peer closed and goes on writing to the rest
     on its schedule, so the writes due while no primary exists are sent,
     timed from their due times and counted;
  c. at the first `node_states()` after the window (the seam cell.py's
     comparison opens with): the survivors' `view_change` blocks are read;
     the victim's domain txn log is read off its disk, without the program
     (benchmarks/reference_store.py); the victim is started again from its
     data directory against the live pool and has to catch up; the client
     redials it; every transaction the victim's disk held at the kill is
     fetched from the SURVIVORS and has to be equal (a prefix: no fork);
  d. cell.py's comparisons then run unchanged over all four validators,
     and tcp_durable's liveness writes after them.

`snapshot()` and `samples()` read the first SURVIVOR; the no-fallback rule
is judged on all three survivors (the configuration's `reads_from`). The
findings reach `correct` through `node_side_problems()`, each printed as a
`compared` line beside its limit, as tcp_durable's do. Every wait here has
a deadline."""
from __future__ import annotations

import asyncio
import contextlib
import os
import signal
import subprocess
import sys
import time

from benchmarks import reference_store
from benchmarks.tcp_client import PoolConnections
from benchmarks.topologies import tcp_durable, tcp_service
from benchmarks.topologies.tcp_durable import AGREE_WAIT_S, compared, say

class SurvivingConnections(PoolConnections):
    """A client of a real pool outlives one node: a connection its peer
    closed is dropped and the writes go on to the rest; `redial` opens it
    again once the node is back."""

    def _drop(self, name: str) -> None:
        conn = self.conns.pop(name, None)
        if conn is not None:
            conn[1].close()

    async def _read(self, name: str) -> None:
        conn = self.conns[name]
        await super()._read(name)       # returns when the peer closed
        if self.conns.get(name) is conn:
            self._drop(name)

    async def flush(self) -> None:
        live = list(self.conns.items())
        outcomes = await asyncio.gather(
            *(asyncio.wait_for(writer.drain(), 10.0)
              for _, (_, writer) in live), return_exceptions=True)
        for (name, conn), outcome in zip(live, outcomes):
            if isinstance(outcome, ConnectionError):
                if self.conns.get(name) is conn:
                    self._drop(name)
            elif isinstance(outcome, BaseException):
                raise outcome
        if not self.conns:
            raise ConnectionError("every node closed its connection")

    async def redial(self, name: str) -> None:
        host, port = self.addrs[name]
        self.conns[name] = await asyncio.wait_for(
            asyncio.open_connection(host, port, limit=1 << 22), 10.0)
        self.readers.append(asyncio.ensure_future(self._read(name)))


def longest_gap(acked: dict, t_open: float, t_close: float) -> dict:
    """The longest interval between two successive acknowledgements inside
    the window -> its seconds and where in the window it began."""
    inside = sorted(t for t in acked.values() if t_open <= t <= t_close)
    if len(inside) < 2:
        return {"reply_gap_s": None, "reply_gap_began_at_s": None}
    seconds, began = max((b - a, a) for a, b in zip(inside, inside[1:]))
    return {"reply_gap_s": seconds, "reply_gap_began_at_s": began - t_open}


class Launcher(tcp_durable.Launcher):
    def __init__(self, config: dict, run_dir: str, seed: int,
                 rehearse: bool):
        fault = dict(config["fault"], **(
            config["fault_rehearsal"] if rehearse else {}))
        # tcp_durable's launcher reads a `crash` block; this deployment
        # states a `fault` instead: it is handed the keys the two share
        # (the restart's deadline, the liveness writes) and no tail
        shared = {k: fault[k] for k in (
            "traffic", "restart_deadline_s", "liveness_writes",
            "liveness_deadline_s")}
        super().__init__(dict(config, crash=dict(shared, tail_writes=0),
                              crash_rehearsal={}), run_dir, seed, rehearse)
        self.fault = fault
        self.victim = None          # chosen before the window opens
        self.reads_from = None      # the first survivor
        self.at_open = None         # {"view_no", "primary"} at window open
        self.window = None          # the window the kill fell into
        self.killed_at = None       # perf_counter, the drive's clock
        self.failover_samples: dict = {}
        self.failover_totals: dict = {}

    # --- start --------------------------------------------------------------

    def start(self, split) -> None:
        super().start(split)
        self.loop.run_until_complete(self.client.close())
        self.client = SurvivingConnections(self.addrs)
        self.loop.run_until_complete(self.client.connect())
        split.mark("client_connect")
        if "view_change" not in self._validator_info(self.names[0]):
            raise SystemExit(
                "benchmark: this program's VALIDATOR_INFO has no "
                "`view_change` block (the parent of this deployment): the "
                "survivors' view change could not be read")

    # --- who dies -----------------------------------------------------------

    @property
    def survivors(self) -> list:
        return [n for n in self.names if n != self.victim]

    def pick_victim(self, primary: str) -> str:
        """The master primary. The tests' seam for the control that kills
        a validator no view change is owed for."""
        return primary

    def _choose_victim(self) -> None:
        infos = [self._validator_info(n) for n in self.names]
        views = {(i["view_no"], i["primaries"][0]) for i in infos}
        if len(views) != 1 or any(i["view_change"]["in_progress"]
                                  for i in infos):
            raise RuntimeError(f"the pool is not in one view before the "
                               f"window: {sorted(views)}")
        view_no, primary = views.pop()
        self.at_open = {"view_no": view_no, "primary": primary}
        self.victim = self.pick_victim(primary)
        self.reads_from = self.survivors[0]

    def kill_victim(self) -> None:
        """The fault, called by the drive loop at its offset. The tests'
        seam for the control in which no kill is sent."""
        os.kill(self.procs[self.names.index(self.victim)].pid,
                signal.SIGKILL)
        self.killed_at = time.perf_counter()

    @contextlib.contextmanager
    def _reading(self, names: list):
        """The inherited readers take "the first node" from `self.names`;
        for the length of one call that list is what this deployment
        reads from."""
        kept, self.names = self.names, names
        try:
            yield
        finally:
            self.names = kept

    # --- traffic ------------------------------------------------------------

    def snapshot(self) -> tuple[dict, list]:
        if self.victim is None:
            self._choose_victim()
        with self._reading([self.reads_from]):
            return super().snapshot()

    def drive(self, requests, schedule, seconds, tracker, drain_s,
              actions=()) -> dict:
        if seconds is None or self.window is not None:
            return super().drive(requests, schedule, seconds, tracker,
                                 drain_s, actions)
        if self.victim is None:
            self._choose_victim()
        at = seconds * self.fault["at_share_of_window"]
        times = super().drive(requests, schedule, seconds, tracker, drain_s,
                              list(actions) + [(at, self.kill_victim)])
        offset = None if self.killed_at is None \
            else self.killed_at - times["t_open"]
        gap = longest_gap(tracker.acked, times["t_open"], times["t_close"])
        self.window = {"seconds": seconds, "kill_offset_s": offset}
        say(fault={"victim": self.victim,
                   "victim_was_primary":
                       self.victim == self.at_open["primary"],
                   "view_no_at_open": self.at_open["view_no"],
                   "kill_offset_s": offset, "kill_due_s": at,
                   **gap, "connections_left": sorted(self.client.conns)})
        if gap["reply_gap_s"] is not None:
            self.failover_samples["failover.reply_gap_s"] = [
                gap["reply_gap_s"]]
        return times

    # --- after the window ---------------------------------------------------

    def node_states(self) -> list:
        if not self.restarted:
            self._after_the_window()
        return tcp_service.Launcher.node_states(self)

    def _judge(self, check: str, got, limit, ok: bool, note: str = "") -> None:
        compared(check, got, limit, ok, note)
        if not ok:
            self.problems.append(f"{check}: {got} (limit {limit}) {note}")

    def _survivors_view_changes(self) -> dict:
        """Every survivor's VALIDATOR_INFO once each has completed a view
        change, or as they are when the deadline passes."""
        deadline = time.monotonic() + self.fault["view_change_deadline_s"]
        while True:
            infos = {n: self._validator_info(n) for n in self.survivors}
            if all(self._in_new_view(i) for i in infos.values()) \
                    or time.monotonic() > deadline:
                return infos
            time.sleep(0.2)

    def _in_new_view(self, info: dict) -> bool:
        vc = info["view_change"]
        return vc["completed"] >= 1 and not vc["in_progress"] \
            and vc["last"] is not None \
            and info["view_no"] > self.at_open["view_no"] \
            and info["last_ordered_3pc"][0] > self.at_open["view_no"]

    def _after_the_window(self) -> None:
        self._judge_the_fault()
        self._read_the_survivors()
        if self.killed_at is None:
            # the control without a kill: nobody is down, nothing to
            # start again; the comparisons run over the four as they are
            self.restarted = self.on_device = True
            return
        # the victim's disk, before it is started again
        on_disk = reference_store.ledger_txns(os.path.join(
            self.run_dir, self.victim, "data", "domain_log"))
        say(on_disk={"victim": self.victim,
                     "domain_txns_on_its_disk": len(on_disk),
                     "preload": len(self.genesis_domain)})
        self._restart_the_victim()
        self._judge_the_prefix(on_disk)
        # the findings above reach `correct` through node_side_problems,
        # in a rehearsal too (tcp_durable's docstring)
        self.on_device = True

    def _judge_the_fault(self) -> None:
        win = self.window
        lo, hi = self.fault["kill_share_limits"]
        share = None if win["kill_offset_s"] is None \
            else round(win["kill_offset_s"] / win["seconds"], 4)
        self._judge("fault.kill_share_of_window", share, [lo, hi],
                    share is not None and lo <= share <= hi,
                    "no kill was sent" if share is None else "")
        was_primary = self.victim == self.at_open["primary"]
        self._judge("fault.victim_was_primary", int(was_primary), 1,
                    was_primary, f"victim {self.victim}, master primary of "
                    f"view {self.at_open['view_no']} "
                    f"{self.at_open['primary']}")

    def _read_the_survivors(self) -> None:
        """Their account of their view change: judged, printed, and kept
        as this kind's samples and totals."""
        infos = self._survivors_view_changes()
        done = [n for n, i in infos.items() if self._in_new_view(i)]
        for name, info in infos.items():
            say(failover={"node": name, "view_no": info["view_no"],
                          "last_ordered_3pc": info["last_ordered_3pc"],
                          "view_change": info["view_change"]})
        self._judge("failover.survivors_ordering_in_a_new_view", len(done),
                    len(self.survivors), len(done) == len(self.survivors),
                    f"of {sorted(infos)}; view at window open "
                    f"{self.at_open['view_no']}")
        lasts = [i["view_change"]["last"]["phases_s"] for i in infos.values()
                 if i["view_change"]["last"]]
        for key, phases in (
                ("detect_to_vote", ("detect_to_vote",)),
                ("vote_to_new_view", ("vote_to_start", "start_to_new_view")),
                ("new_view_to_order", ("new_view_to_order",))):
            got = [sum(p[x] for x in phases) for p in lasts
                   if all(x in p for x in phases)]
            if got:
                self.failover_samples[f"failover.{key}_s"] = got
        self.failover_totals = {
            "failover.view_changes_started": sum(
                i["view_change"]["started"] for i in infos.values()),
            "failover.survivors": len(infos)}

    def _restart_the_victim(self) -> None:
        """From its data directory, against the live pool; then the client
        redials it and the four have to reach one view."""
        fault = self.fault
        i = self.names.index(self.victim)
        self.procs[i].wait(timeout=30.0)
        t0 = time.perf_counter()
        out = os.path.join(self.run_dir, f"{self.victim}.life2.out")
        with open(out, "wb") as log:
            self.procs[i] = subprocess.Popen(
                [sys.executable, "-m", "plenum_tpu.tools.start_node",
                 "--name", self.victim, "--base-dir", self.run_dir,
                 "--kv", self.config["kv"], "--backend", "service"],
                env=self._env(), cwd=self.run_dir, stdout=log,
                stderr=subprocess.STDOUT)
        line = self._wait_line(out, self.procs[i], b'{"started"',
                               fault["restart_deadline_s"])
        restart_s = time.perf_counter() - t0
        self.restarted = True
        recovery = line.get("recovery") or {}
        caught_up = (recovery.get("rejoined") or {}).get(
            "txns_caught_up") or {}
        say(rejoin={"victim": self.victim, "engine": line.get("engine"),
                    "restart_s": round(restart_s, 3),
                    "catchup_txns": sum(caught_up.values()),
                    "catchup_txns_by_ledger": caught_up,
                    "recovery": recovery})
        self._judge("rejoin.engine_is_the_stated_one",
                    line.get("engine"), self.config["kv_engine"],
                    line.get("engine") == self.config["kv_engine"])
        self._judge("rejoin.restart_s", round(restart_s, 3),
                    fault["restart_deadline_s"],
                    restart_s <= fault["restart_deadline_s"])
        self.loop.run_until_complete(self.client.redial(self.victim))

        deadline = time.monotonic() + AGREE_WAIT_S
        while True:
            states = tcp_service.Launcher.node_states(self)
            if len({(s["domain_size"], s["domain_root"]) for s in states}) \
                    == 1 or time.monotonic() > deadline:
                break
            time.sleep(0.2)

    def _judge_the_prefix(self, on_disk: dict) -> None:
        """No fork: what the victim's disk held at the kill is what the
        survivors hold at the same sequence numbers."""
        seqs = sorted(s for s in on_disk if s > len(self.genesis_domain))
        theirs = self._fetch_from(self.survivors, seqs)
        differ = [s for s in seqs if theirs.get(s) != on_disk[s]]
        self._judge("failover.victim_disk_txns_differing_from_survivors",
                    len(differ), 0, not differ and bool(seqs),
                    f"of {len(seqs)} past the preload; first {differ[:3]}"
                    if differ or not seqs else f"of {len(seqs)}")

    def _fetch_from(self, names: list, seq_nos: list) -> dict:
        """tcp_service's fetch_txns over `names` only: each transaction
        from ONE of them with its Merkle proof verified client-side."""
        kept, self.addrs = self.addrs, {n: self.addrs[n] for n in names}
        try:
            with self._reading(names):
                return self.fetch_txns(seq_nos)
        finally:
            self.addrs = kept

    # --- the end ------------------------------------------------------------

    def node_side_problems(self) -> list:
        """The no-fallback rule over the three survivors, whose one life
        spans the run (the victim's first life ended unflushed), and this
        kind's own findings. In a rehearsal the nodes have no device plane
        to judge."""
        base = [] if self.rehearse else [
            p for p in tcp_service.Launcher.node_side_problems(self)
            if not p.startswith(f"{self.victim}:")]
        return base + self.problems

    def samples(self) -> tuple[dict, dict]:
        """The first survivor's samples over its one life (warm-up, the
        window, the comparisons' reads, the liveness writes), and this
        kind's own."""
        i = self.names.index(self.reads_from)
        folds, self.metrics_folds = self.metrics_folds, \
            [self.metrics_folds[i]]
        try:
            samples, totals = tcp_service.Launcher.samples(self)
        finally:
            self.metrics_folds = folds
        samples["storage.flush_s"] = folds[i].get(
            "storage.flush_time", {}).get("samples", [])
        samples.update(self.failover_samples)
        totals.update(self.failover_totals)
        return samples, totals
