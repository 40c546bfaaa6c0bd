"""Launcher kind `tcp_rejoin`: tcp_durable's pool with one validator that is
no instance's primary SIGKILLed early in the timed window and started again
at once, so that it replays its stores, catches up and votes again while
the client keeps writing.

Start, service, stores, client accounting, the kill as a timed action of
`drive`, the client that outlives a node, the reading of a disk and the
liveness writes are tcp_failover's, tcp_durable's and tcp_service's
launchers, inherited. What this kind adds:

  a. before the window opens every validator's VALIDATOR_INFO names the
     view and the primaries of its instances: the victim is the last
     validator that is none of them;
  b. a second timed action, `restart_after_s` after the kill: the restart.
     The drive loop only starts a thread. The thread reaps the killed
     process, copies its domain txn log as the kill left it, starts the
     same command line on the same data directory (a Popen), watches the
     second life's output for the start line and then has the client's
     loop redial the victim, inside the window;
  c. at the first `node_states()` after the window (the seam cell.py's
     comparison opens with): all four VALIDATOR_INFOs are read (the
     victim's `rejoin` block, every node's `catchup.seeder` and
     `view_change`), one verified read from each survivor says whether the
     victim's COMMIT is in its last multi-signature, the copy of the
     victim's log is read without the program (reference_store.py) and
     held against the final ledger (reference_rejoin.py), and the
     deployment's guarantees are judged, each number beside its limit;
  d. cell.py's comparisons then run unchanged over all four validators,
     and tcp_durable's liveness writes after them.

`snapshot()` and `samples()` read the FIRST validator (the master primary,
as the steady cells do), its `seeder.*` series included; the no-fallback
rule is judged on all four, the victim over the metrics its second life
flushed. The findings reach `correct` through `node_side_problems()`, as
tcp_durable's do. Every wait here has a deadline."""
from __future__ import annotations

import asyncio
import os
import shutil
import subprocess
import sys
import threading
import time

from benchmarks import reference_rejoin, reference_store
from benchmarks.topologies import tcp_failover, tcp_service
from benchmarks.topologies.tcp_durable import AGREE_WAIT_S, say

PHASES = ("process_start", "stores_replayed", "peers_reachable",
          "catchup_started", "catchup_complete", "first_3pc_order")
# what a write's REPLY adds to the transaction as the ledger holds it
PROOF_KEYS = ("rootHash", "auditPath", "ledgerSize", "merkle_proof",
              "state_proof")


def require_program() -> None:
    """The parent of this deployment has no rejoin clock and no seeder
    series: refused before anything is started."""
    from plenum_tpu.common.metrics import MetricsName
    if not hasattr(MetricsName, "SEEDER_SERVE_TIME"):
        raise SystemExit(
            "benchmark: this program has no `rejoin` block in "
            "VALIDATOR_INFO and no seeder.* series (the parent of this "
            "deployment): a restart inside the window could not be read")


class RejoinConnections(tcp_failover.SurvivingConnections):
    """SurvivingConnections that also keeps when ONE node's REPLY to each
    request arrived, whether or not it came after the quorum: the
    victim's REPLY to a write says it executed that write's batch through
    its own ordering (a transaction taken by catch-up answers nobody)."""

    def __init__(self, addrs: dict):
        super().__init__(addrs)
        self.watch = None
        self.replies_from_watched: dict = {}

    def _on_message(self, name: str, msg: dict) -> None:
        if name == self.watch and msg.get("op") == "REPLY":
            meta = msg.get("result", {}).get("txn", {}).get("metadata", {})
            self.replies_from_watched.setdefault(
                (meta.get("from"), meta.get("reqId")), time.perf_counter())
        super()._on_message(name, msg)


class Launcher(tcp_failover.Launcher):
    def __init__(self, config: dict, run_dir: str, seed: int,
                 rehearse: bool):
        require_program()
        super().__init__(config, run_dir, seed, rehearse)
        self.times = None           # the window's, from the drive
        self.window_tracker = None
        self.restarter = None       # the thread of b.
        self.life2: dict = {}       # what it did and saw, by perf_counter
        self.rejoin_samples: dict = {}
        self.rejoin_totals: dict = {}

    # --- start --------------------------------------------------------------

    def start(self, split) -> None:
        super().start(split)
        self.loop.run_until_complete(self.client.close())
        self.client = RejoinConnections(self.addrs)
        self.loop.run_until_complete(self.client.connect())
        split.mark("client_connect")

    # --- who dies, and comes back -------------------------------------------

    def pick_victim(self, primary: str) -> str:
        """The last validator that is primary of no instance."""
        return [n for n in self.names
                if n not in self.at_open["primaries"]][-1]

    def _choose_victim(self) -> None:
        infos = [self._validator_info(n) for n in self.names]
        views = {(i["view_no"], tuple(i["primaries"])) for i in infos}
        if len(views) != 1 or any(i["view_change"]["in_progress"]
                                  for i in infos):
            raise RuntimeError(f"the pool is not in one view before the "
                               f"window: {sorted(views)}")
        view_no, primaries = views.pop()
        self.at_open = {"view_no": view_no, "primary": primaries[0],
                        "primaries": list(primaries)}
        self.victim = self.pick_victim(primaries[0])
        self.reads_from = self.names[0]
        self.client.watch = self.victim

    @property
    def disk_copy(self) -> str:
        return os.path.join(self.run_dir, f"{self.victim}.at_kill",
                            "domain_log")

    def after_the_kill(self) -> None:
        """Between the reaping of the killed process and the copy of its
        log: nothing. The tests' seam for what a disk can hold or lose
        while its validator is down."""

    def restart_victim(self) -> None:
        """The restart, called by the drive loop at its offset: a thread
        is started and nothing is waited for. The tests' seam for the
        control in which the victim is not started again."""
        if self.killed_at is None or self.restarter is not None:
            return
        self.restarter = threading.Thread(target=self._second_life,
                                          daemon=True)
        self.restarter.start()

    def _second_life(self) -> None:
        life, i = self.life2, self.names.index(self.victim)
        try:
            self.procs[i].wait(timeout=30.0)
            self.after_the_kill()
            shutil.copytree(os.path.join(self.run_dir, self.victim, "data",
                                         "domain_log"), self.disk_copy)
            out = os.path.join(self.run_dir, f"{self.victim}.life2.out")
            # the action was due restart_after_s after the kill's DUE
            # time; the kill itself may have been sent a little late
            time.sleep(max(0.0, self.killed_at + self.fault[
                "restart_after_s"] - time.perf_counter()))
            life["spawned_at"] = time.perf_counter()
            with open(out, "wb") as log:
                self.procs[i] = subprocess.Popen(
                    [sys.executable, "-m", "plenum_tpu.tools.start_node",
                     "--name", self.victim, "--base-dir", self.run_dir,
                     "--kv", self.config["kv"], "--backend", "service"],
                    env=self._env(), cwd=self.run_dir, stdout=log,
                    stderr=subprocess.STDOUT)
            life["line"] = self._wait_line(
                out, self.procs[i], b'{"started"',
                self.fault["restart_deadline_s"])
            life["started_at"] = time.perf_counter()
            self.loop.call_soon_threadsafe(self._redial)
        except Exception as e:      # read by _after_the_window
            life["error"] = repr(e)

    def _redial(self) -> None:
        """On the client's loop, whenever it next runs."""
        if self.victim in self.client.conns or "redialing" in self.life2:
            return
        self.life2["redialing"] = True

        async def redial():
            await self.client.redial(self.victim)
            self.life2["redialed_at"] = time.perf_counter()
        asyncio.ensure_future(redial(), loop=self.loop)

    # --- traffic ------------------------------------------------------------

    def snapshot(self) -> tuple[dict, list]:
        """tcp_durable's, and what the first validator's seeder has cost
        it so far beside its own clock."""
        counters, sups = super().snapshot()
        info = self._validator_info(self.names[0])
        seeder = (info.get("catchup") or {}).get("seeder")
        if seeder:
            counters.update({"seeder.serve_seconds": seeder["serve"]["sum_s"],
                             "seeder.clock_seconds": info["uptime"]})
        return counters, sups

    def drive(self, requests, schedule, seconds, tracker, drain_s,
              actions=()) -> dict:
        if seconds is None or self.window is not None:
            return super().drive(requests, schedule, seconds, tracker,
                                 drain_s, actions)
        if self.victim is None:
            self._choose_victim()
        self.window_tracker = tracker
        at = seconds * self.fault["at_share_of_window"] \
            + self.fault["restart_after_s"]
        self.times = super().drive(
            requests, schedule, seconds, tracker, drain_s,
            list(actions) + [(at, self.restart_victim)])
        return self.times

    # --- after the window ---------------------------------------------------

    def _after_the_window(self) -> None:
        self._judge_the_fault()
        if not self._second_life_started():
            # never started (the control), so nothing of the rejoin can
            # be read; the comparisons need four validators all the same
            if self.killed_at is not None:
                self._restart_the_victim()
            self.restarted = self.on_device = True
            return
        if self.victim not in self.client.conns:
            self.loop.run_until_complete(self.client.redial(self.victim))
        self.restarted = True
        states = self._wait_for_one_view()
        infos = {n: self._validator_info(n) for n in self.names}
        self._judge_the_rejoin(infos)
        self._judge_the_pool(infos)
        self._judge_the_prefix(states)
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
        primaries = self.at_open["primaries"]
        self._judge("fault.victim_is_primary_of_instances",
                    primaries.count(self.victim), 0,
                    self.victim not in primaries,
                    f"victim {self.victim}; primaries of view "
                    f"{self.at_open['view_no']}: {primaries}")

    def _second_life_started(self) -> bool:
        """Wait (with the deployment's deadline) for the thread of b.;
        -> whether the victim's second life printed its start line."""
        limit = self.fault["restart_deadline_s"]
        if self.restarter is not None:
            self.restarter.join(limit + 35.0)
        life = self.life2
        started = "started_at" in life
        down_s = life["started_at"] - self.killed_at if started else None
        self._judge("rejoin.down_s", down_s and round(down_s, 3), limit,
                    started and down_s <= limit,
                    life.get("error") or ("" if started else
                                          "the victim was not started again"))
        if "spawned_at" in life and not started:
            raise RuntimeError(f"the victim's second life gave no start "
                               f"line: {life.get('error')}")
        return started

    def _wait_for_one_view(self) -> list:
        deadline = time.monotonic() + AGREE_WAIT_S
        while True:
            states = tcp_service.Launcher.node_states(self)
            if len({(s["domain_size"], s["domain_root"]) for s in states}) \
                    == 1 or time.monotonic() > deadline:
                return states
            time.sleep(0.2)

    def _judge_the_rejoin(self, infos: dict) -> None:
        """The victim's own account of its second life, on the window's
        clock: its phases count from the instant the kernel made the
        process, on the monotonic clock this process reads too."""
        life, times, fault = self.life2, self.times, self.fault
        line = life["line"]
        rejoin = infos[self.victim].get("rejoin") or {}
        phases = rejoin.get("phases_s") or {}
        recovery = line.get("recovery") or {}
        caught_up = (recovery.get("rejoined") or {}).get(
            "txns_caught_up") or {}
        t_open, t_drained = times["t_open"], times["t_drained"]
        replies = [t for key, t in self.client.replies_from_watched.items()
                   if t <= t_drained and key in self.window_tracker.due]
        say(rejoin={
            "victim": self.victim, "engine": line.get("engine"),
            "restart_due_after_s": fault["restart_after_s"],
            "restart_delay_s": round(life["spawned_at"] - self.killed_at, 4),
            "down_s": round(life["started_at"] - self.killed_at, 3),
            "start_line_at_s": round(life["started_at"] - t_open, 3),
            "redialed_at_s": round(life["redialed_at"] - t_open, 3)
            if "redialed_at" in life else None,
            "window_closed_at_s": round(times["t_close"] - t_open, 3),
            "drain_ended_at_s": round(t_drained - t_open, 3),
            "phases_s": phases, "rounds": rejoin.get("rounds"),
            "stash": rejoin.get("stash"),
            "catchup_txns": sum(caught_up.values()),
            "catchup_txns_by_ledger": caught_up,
            "last_ordered_3pc": infos[self.victim]["last_ordered_3pc"],
            "replies_to_window_writes_by_drain_end": len(replies),
            "recovery": recovery})
        self._judge("rejoin.engine_is_the_stated_one", line.get("engine"),
                    self.config["kv_engine"],
                    line.get("engine") == self.config["kv_engine"])
        stamps = [phases.get(p) for p in PHASES]
        disorder = sum(1 for a, b in zip(stamps, stamps[1:])
                       if a is None or b is None or a > b)
        self._judge("rejoin.phases_missing_or_out_of_order", disorder, 0,
                    disorder == 0, f"{phases}" if disorder else "")
        first = phases.get("first_3pc_order")
        at = None if first is None \
            else round(rejoin["t0"] + first - t_open, 3)
        limit = round(t_drained - t_open, 3)
        self._judge("rejoin.first_3pc_order_at_s", at, limit,
                    at is not None and at <= limit,
                    "the victim ordered nothing by its own COMMIT quorum"
                    if at is None else "of the window; limit = drain end")
        self._judge("rejoin.victim_replies_to_window_writes", len(replies),
                    1, len(replies) >= 1, "received before the drain ended")
        counted = self._multi_signatures_counting_the_victim()
        self._judge("rejoin.survivors_whose_last_multi_sig_counts_victim",
                    counted, 1, counted >= 1, f"of {len(self.survivors)}")
        if disorder:
            return
        seconds = phases["catchup_complete"] - phases["catchup_started"]
        self.rejoin_samples = {
            "rejoin.down_s": [life["started_at"] - self.killed_at],
            "rejoin.boot_s": [phases["peers_reachable"]],
            "rejoin.catchup_s": [seconds],
            "rejoin.to_first_order_s": [first - phases["catchup_complete"]]}
        self.rejoin_totals = {
            "rejoin.catchup_txns": sum(caught_up.values()),
            "rejoin.catchup_seconds": seconds,
            "rejoin.catchup_rounds": len(rejoin["rounds"]),
            "rejoin.rejoins": 1}

    def _multi_signatures_counting_the_victim(self) -> int:
        """One verified read answered by each survivor, after the pool
        went quiet -> how many of their multi-signatures over the last
        root list the victim among the signers. The reader starts its
        ladder at a node its request's digest picks: request ids are tried
        until each survivor has been the first rung."""
        from plenum_tpu.common.request import Request
        from plenum_tpu.execution.txn import GET_NYM
        from plenum_tpu.reads.client import ladder_order
        reader, wanted, asked = self._reader(), set(self.survivors), []
        for req_id in range(4 * 10 ** 9, 4 * 10 ** 9 + 64):
            req = Request("bench-reader", req_id, {
                "type": GET_NYM, "dest": self.ids.did(0).identifier})
            first = ladder_order(list(self.addrs), req)[0]
            if first in wanted:
                wanted.discard(first)
                asked.append(req)

        async def run():
            counted = 0
            try:
                for req in asked:
                    before = reader.stats.single_reply_ok
                    msg = await reader.submit_read(req, timeout=20.0)
                    if reader.stats.single_reply_ok != before + 1 \
                            or reader.stats.failovers:
                        continue    # not the one reply of the first rung
                    proof = (msg.get("result") or {}).get("read_proof") or {}
                    signers = (proof.get("multi_signature") or [0, []])[1]
                    counted += self.victim in signers
            finally:
                await reader.close()
            return counted
        return self.loop.run_until_complete(run())

    def _judge_the_pool(self, infos: dict) -> None:
        """No election for a backup, and the seeders' account against the
        leecher's."""
        started = {n: i["view_change"]["started"] for n, i in infos.items()}
        self._judge("rejoin.view_changes_started", sum(started.values()), 0,
                    not any(started.values()), f"{started}")
        elsewhere = [n for n, i in infos.items()
                     if i["view_no"] != self.at_open["view_no"]]
        self._judge("rejoin.validators_in_another_view", len(elsewhere), 0,
                    not elsewhere, f"{elsewhere}" if elsewhere else "")
        seeders = {n: (i.get("catchup") or {}).get("seeder") or {}
                   for n, i in infos.items()}
        say(seeders=seeders)
        served = sum(s.get("txns_served", 0) for n, s in seeders.items()
                     if n != self.victim)
        taken = self.rejoin_totals.get("rejoin.catchup_txns", 0)
        self._judge("rejoin.txns_caught_up_minus_txns_served",
                    taken - served, 0, 0 < taken <= served,
                    f"{taken} taken, {served} served by the survivors")
        self.rejoin_totals.update({
            "rejoin.view_changes_started": sum(started.values()),
            "rejoin.validators": len(infos)})

    def _final_ledger(self, size: int) -> list:
        """The agreed ledger's transactions in order: the preload, what
        the replies of every drive carried, and the rest fetched from the
        survivors with their proofs verified."""
        known = {}
        for tracker in self.trackers:
            for result in tracker.results.values():
                seq = (result.get("txnMetadata") or {}).get("seqNo")
                if seq is not None:
                    known[seq] = {k: v for k, v in result.items()
                                  if k not in PROOF_KEYS}
        genesis = self.genesis_domain
        holes = [s for s in range(len(genesis) + 1, size + 1)
                 if s not in known]
        known.update(self._fetch_from(self.survivors, holes))
        return genesis + [known[s] for s in range(len(genesis) + 1, size + 1)]

    def _judge_the_prefix(self, states: list) -> None:
        """No fork: the victim's disk at the kill, read without the
        program, is a prefix of the ledger the four ended on."""
        on_disk = reference_store.ledger_txns(self.disk_copy)
        final = self._final_ledger(min(s["domain_size"] for s in states))
        got = reference_rejoin.prefix_check(on_disk, final)
        differing = got.pop("differing")
        say(on_disk=dict(got, victim=self.victim,
                         preload=len(self.genesis_domain),
                         differing=len(differing)))
        past = got["disk_txns"] - len(self.genesis_domain)
        self._judge("rejoin.victim_disk_txns_differing_from_final",
                    len(differing) + got["disk_longer_than_final"]
                    + got["disk_txns_past_a_hole"], 0,
                    not differing and not got["disk_longer_than_final"]
                    and not got["disk_txns_past_a_hole"] and past > 0,
                    f"of {past} past the preload; first {differing[:3]}")
        roots = [got["disk_root"] != got["final_prefix_root"]] + [
            s["domain_root"] != got["final_root"] for s in states]
        self._judge("rejoin.victim_disk_prefix_root_mismatches",
                    sum(roots), 0, not any(roots),
                    f"disk {got['disk_root'][:16]} against the final "
                    f"ledger's first {got['disk_txns']}: "
                    f"{got['final_prefix_root'][:16]}")

    # --- the end ------------------------------------------------------------

    def node_side_problems(self) -> list:
        """The no-fallback rule over all four: the survivors' one life
        and what the victim's second flushed (its first ended unflushed).
        In a rehearsal the nodes have no device plane to judge."""
        base = [] if self.rehearse \
            else tcp_service.Launcher.node_side_problems(self)
        return base + self.problems

    def samples(self) -> tuple[dict, dict]:
        samples, totals = super().samples()
        samples["seeder.serve_s"] = self.metrics_folds[0].get(
            "seeder.serve_time", {}).get("samples", [])
        samples.update(self.rejoin_samples)
        totals.update(self.rejoin_totals)
        return samples, totals
