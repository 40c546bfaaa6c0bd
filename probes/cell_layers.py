"""Builder's probe: one benchmark cell run that also says, on an earlier
line, what an untraced run can already count.

A `--trace 0` run of the benchmark reports only end-to-end metrics, and a
traced run costs five minutes of writing the trace out. This stand-in for
`benchmarks/cell.py` runs the same cell and, just before the result, prints
`probe_per_layer` (every per-layer metric of the cell whose reader finds its
counters or samples without a trace), `probe_cuts` (the first
validator's cut-reason counts, `consensus.batch_cut_*`: flushed store when
the nodes are processes, the node's controller when they are in process;
null on a checkout that has no such counter) and `probe_lanes` (the
window's device dispatches by the lane count of the program that ran each,
`dispatches_by_lanes` of the service's stats or of the rings' summaries,
with the smallest program's share; null on a checkout older than PR 38),
`probe_stages` (the first validator's stage clock, `stage.*`: each stage's
count and mean over the window, from the growth of its cumulative count and
sum in VALIDATOR_INFO `stages`; p50 and p95 from the flushed store where
the nodes are processes, which holds the warm-up's 64 writes too, else
from the node's reservoir; the sum of the seven waits' means beside the
mean residence, the same over the requests that had every stage (`whole`),
and the client's p50 beside the residence's) and `probe_service_waits`
(the crypto service's `waits`: a job's time on its queue and from there to
its verdicts, window growth for the means); both null on a checkout older
than PR 39; `probe_transport` (the first validator's VALIDATOR_INFO
`transport` over the window: frames written by who flushed them,
`in_cycle` or `scheduled`, and the share the cycles' own flush points took;
`tx_hold`, a frame's oldest message from `_enqueue_send` to the socket
write, and `rx_hold`, a message from its frame's decode to the bus; the
looper's `wakes` by cause, `busy`, `arrival`, `interval`; null where the
nodes share a process and on a checkout older than PR 40) and
`probe_round` (the window's batch cuts on the first validator, from
VALIDATOR_INFO `batch_controller.cuts`: cuts a second and the ms one 3PC
round takes while the pool orders one batch at a time) and `probe_bls`
(EVERY validator's VALIDATOR_INFO `bls` over the window, by name: the
primary validates none of its own PRE-PREPAREs, so the first alone says
nothing of `ppr_multi_sig`; a validator that is down at either end of the
window reads null. Order-time
checks of COMMIT signatures that went to the BLS library's worker thread,
`offloaded`, or were settled at the submit, `inline`; `join_wait`, what
the node's loop blocked for the worker in a landing, `verify`, the
checks' own duration, and `verify_late`, those of them a late COMMIT asked
for, each with count and mean; `hidden_share` = 1 - the
summed join wait over the summed check time; null on a checkout older
than PR 48; since PR 49 `ppr_multi_sig`: of the multi-signatures the
PRE-PREPAREs it validated carried, those it `known` and those it `paired`
on, `paired_share`, the pairings' mean ms and the ms it first blocked
landing its own check, `joined_ms`) and, in the failover cell,
`probe_failover_steps` (each survivor's `view_change.ordering` episode as
the steps between NEW_VIEW accepted and the first fresh order, ms:
`recertify` + `first_cut` + `first_round` = `fresh_order`, the kind of
its first order, its cycles and BLS landings, and the medians over the
survivors: what six per-layer metrics would read; null on a checkout
older than PR 49) and `probe_propagation` (every validator's
`propagation.forwarded_after_executed` over the window: requests it
queued for ordering after it had executed them, which a node that becomes
primary proposes again). With `--trace 1`
it keeps the run's xplane under
`chiprun_out/probe_trace/` (where it is under 24 MiB) and prints
`probe_gaps`: each device idle gap of the sample with the host spans
(`prod.*`, `ring.*`, `svc.*`) that overlap it and the share of it each
covers. Nothing of the benchmark is changed: the last line is run.py's own.

    python3 probes/cell_layers.py --workload <cell> --seed <n> --seconds 20
"""
from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REASONS = ("full", "idle", "timeout", "forced")


def cut_counts(topo) -> dict | None:
    folds = getattr(topo, "metrics_folds", None)
    if folds:
        found = {r: folds[0].get(f"consensus.batch_cut_{r}", {}).get("max")
                 for r in REASONS}
        return {r: int(v or 0) for r, v in found.items()} \
            if any(v is not None for v in found.values()) else None
    pool = getattr(topo, "pool", None)
    if pool is not None:
        ctl = pool.nodes[topo.names[0]].batch_controller
        return ctl.trajectory().get("cuts") if ctl is not None else None
    return None


def by_lanes(topo) -> dict | None:
    """{lanes: dispatches so far} of the cell's device plane: the crypto
    service's, the in-process ring's, or the four owners' summed (from the
    VALIDATOR_INFO answers a snapshot has just fetched: `last_infos`)."""
    if hasattr(topo, "last_infos"):
        found = [(info.get("plane") or {}).get("ring", {})
                 .get("dispatches_by_lanes") for info in topo.last_infos]
    elif getattr(topo, "pool", None) is not None:
        pipe = topo.pool.pipeline
        found = [pipe.summary().get("dispatches_by_lanes")
                 if pipe is not None else None]
    else:
        found = [topo.plane.stats().get("dispatches_by_lanes")]
    if any(f is None for f in found):
        return None
    out: dict = {}
    for f in found:
        for lanes, n in f.items():
            out[int(lanes)] = out.get(int(lanes), 0) + n
    return out


def window_lanes(seen: list) -> dict | None:
    """The growth between a window's two snapshots, and the share of it
    the smallest program took."""
    if len(seen) < 2 or seen[0] is None or seen[1] is None:
        return None
    grew = {k: seen[1][k] - seen[0].get(k, 0) for k in sorted(seen[1])}
    total = sum(grew.values())
    return {"dispatches": {str(k): v for k, v in grew.items()},
            "smallest_program_share": round(grew[min(grew)] / total, 4)
            if total else None}      # a host plane dispatches no program


def validator_infos(topo) -> dict:
    """{name: VALIDATOR_INFO as it stands now} of every validator that
    answers (of nodes in this process: the parts of it the probe reads),
    the one the cell reads from first."""
    if hasattr(topo, "last_infos"):             # four owners: just fetched
        return dict(zip(topo.names, topo.last_infos))
    pool = getattr(topo, "pool", None)
    if pool is not None:                        # nodes in this process
        out = {}
        for name in topo.names:
            node = pool.nodes[name]
            clock = getattr(node, "stages", None)
            ctl = node.batch_controller
            bls = getattr(node.master_replica, "bls", None)
            out[name] = {
                "stages": clock.report() if clock is not None else None,
                "batch_controller": ctl.trajectory() if ctl is not None
                else None,
                # snapshots: the records keep counting
                "bls": copy.deepcopy(getattr(bls, "stats", None)),
                "propagation": dict(node.propagator.stats)
                if hasattr(node.propagator, "stats") else None}
        return out
    from benchmarks.tcp_client import ask
    from plenum_tpu.execution.action_manager import VALIDATOR_INFO_ACTION
    # the failover cell kills the first validator: it reads from the
    # first survivor, and so does the probe
    first = getattr(topo, "reads_from", None) or topo.names[0]
    out = {}
    for name in [first] + [n for n in topo.names if n != first]:
        try:
            msg = topo.loop.run_until_complete(ask(
                topo.addrs[name],
                topo._trustee_request({"type": VALIDATOR_INFO_ACTION})))
        except (OSError, EOFError):
            continue                # down: a cell's victim
        out[name] = (msg.get("result") or {}).get("data") or {}
    return out


def service_waits(topo) -> dict | None:
    plane = getattr(topo, "plane", None)
    return plane.stats().get("waits") if plane is not None else None


def grown(seen: list) -> dict | None:
    """{name: {"count", "mean_ms"}} between a window's two readings of
    cumulative {"count", "sum_s"} records."""
    if len(seen) < 2 or not seen[0] or not seen[1]:
        return None
    out = {}
    for name, end in seen[1].items():
        start = seen[0].get(name) or {"count": 0, "sum_s": 0.0}
        n = end["count"] - start["count"]
        out[name] = {"count": n, "mean_ms": round(
            (end["sum_s"] - start["sum_s"]) / n * 1e3, 4) if n else None}
    return out


def window_stages(seen: list, topo, numbers: dict) -> dict | None:
    stages = grown(seen)
    if stages is None:
        return None
    whole = stages.pop("whole")
    from plenum_tpu.common.metrics import percentile
    folds = (getattr(topo, "metrics_folds", None) or [{}])[0]
    for name, row in stages.items():
        samples = folds.get(name, {}).get("samples")
        for q, key in ((0.5, "p50_ms"), (0.95, "p95_ms")):
            row[key] = (round(percentile(samples, q) * 1e3, 4) if samples
                        else seen[1][name].get(key))
    waits = [v["mean_ms"] for k, v in stages.items()
             if k != "stage.residence"]
    return {"stages": stages,
            "sum_of_wait_means_ms": round(sum(waits), 4)
            if all(w is not None for w in waits) else None,
            "residence_mean_ms": stages["stage.residence"]["mean_ms"],
            "whole": dict(whole, residence_count=stages[
                "stage.residence"]["count"]),
            "residence_p50_ms": stages["stage.residence"]["p50_ms"],
            "client_p50_ms": numbers.get("latency_p50_ms")}


def window_transport(seen: list) -> dict | None:
    """When a message left and when a frame was seen, over the window:
    the growth between two readings of VALIDATOR_INFO `transport`."""
    if len(seen) < 2 or not seen[0] or not seen[1]:
        return None
    start, end = seen

    def grew(group):
        return {k: end[group][k] - start[group].get(k, 0)
                for k in end[group]}
    flushes = grew("flushes")
    frames = end["sent_frames"] - start["sent_frames"]
    return dict(
        grown([{k: r[k] for k in ("tx_hold", "rx_hold")} for r in seen]),
        sent_frames=frames,
        recv_frames=end["recv_frames"] - start["recv_frames"],
        flushes=flushes,
        in_cycle_share=round(flushes["in_cycle"] / frames, 4)
        if frames else None,
        wakes=grew("wakes"))


def window_round(seen: list, numbers: dict) -> dict | None:
    """Batch cuts over the window on the first validator, and what one
    3PC round takes while the pool orders one batch at a time."""
    cuts = [((info or {}).get("batch_controller") or {}).get("cuts")
            for info in seen]
    if len(cuts) < 2 or cuts[0] is None or cuts[1] is None:
        return None
    n = sum(cuts[1].values()) - sum(cuts[0].values())
    # the window's seconds, as window_numbers divided by them
    seconds = (numbers["acked_in_window"] / numbers["per_s"]
               if numbers.get("per_s") else None)
    return {"cuts": n,
            "cuts_per_s": round(n / seconds, 2) if seconds else None,
            "round_ms": round(seconds / n * 1e3, 2) if seconds and n
            else None}


def window_bls(seen: list) -> dict | None:
    """The order-time BLS checks over the window, and the share of their
    time that ran beside the node's loop."""
    if len(seen) < 2 or not seen[0] or not seen[1]:
        return None
    start, end = seen
    times = grown([{k: r[k] for k in ("join_wait", "verify", "verify_late")}
                   for r in seen])
    waited = end["join_wait"]["sum_s"] - start["join_wait"]["sum_s"]
    checked = end["verify"]["sum_s"] - start["verify"]["sum_s"]
    return dict(times,
                offloaded=end["offloaded"] - start["offloaded"],
                inline=end["inline"] - start["inline"],
                hidden_share=round(1 - waited / checked, 4)
                if checked else None,
                ppr_multi_sig=window_ppr_multi_sig(seen))


def window_ppr_multi_sig(seen: list) -> dict | None:
    """What became of the multi-signatures the PRE-PREPAREs this validator
    validated over the window carried (`bls.ppr_multi_sig`, PR 49)."""
    start, end = (r.get("ppr_multi_sig") for r in seen)
    if not start or not end:
        return None
    grew = {k: end[k] - start[k] for k in end}
    carried = grew["known"] + grew["paired"]
    return {"known": grew["known"], "paired": grew["paired"],
            "paired_share": round(grew["paired"] / carried, 4)
            if carried else None,
            "paired_mean_ms": round(
                grew["paired_s"] / grew["paired"] * 1e3, 4)
            if grew["paired"] else None,
            "joined_ms": round(grew["joined_s"] * 1e3, 3)}


def window_propagation(seen: list) -> dict | None:
    """Requests a validator queued for ordering over the window after it
    had executed them (`propagation`, PR 49)."""
    if len(seen) < 2 or not seen[0] or not seen[1]:
        return None
    return {k: seen[1][k] - seen[0][k] for k in seen[1]}


def failover_steps(infos: dict) -> dict | None:
    """{survivor: its episode as steps} and the medians over them, from
    the survivors' VALIDATOR_INFO read after the window."""
    from statistics import median
    rows = {}
    for name, info in infos.items():
        vc = info.get("view_change") or {}
        e = vc.get("ordering") or {}
        if "fresh_ordered_ms" not in e:
            return None             # a checkout older than PR 49
        ends = [e["recertified_ms"], e["first_cut_apply_ms"],
                e["fresh_ordered_ms"]]
        whole = None not in ends
        rows[name] = {
            "recertify_ms": e["recertified_ms"],
            "first_cut_ms": round(ends[1] - ends[0], 3) if whole else None,
            "first_round_ms": round(ends[2] - ends[1], 3) if whole else None,
            "fresh_order_ms": e["fresh_ordered_ms"],
            "bls_join_wait_ms": e["bls"].get("join_wait_ms"),
            "first_ordered_kind": e["first_ordered_kind"],
            # since its start, read after the drain
            "forwarded_after_executed": (info.get("propagation") or {}).get(
                "forwarded_after_executed"),
            # the one stamp pair of before, on the node's latched timer
            "new_view_to_order_ms": round(((vc.get("last") or {}).get(
                "phases_s") or {}).get("new_view_to_order", 0) * 1e3, 3),
            "episode": e}
    if not rows:
        return None
    medians = {}
    for key in ("recertify_ms", "first_cut_ms", "first_round_ms",
                "fresh_order_ms", "bls_join_wait_ms"):
        got = [r[key] for r in rows.values() if r[key] is not None]
        medians["failover." + key] = median(got) if got else None
    medians["failover.first_order_fresh_share"] = 100.0 * sum(
        r["first_ordered_kind"] == "fresh" for r in rows.values()) / len(rows)
    return {"survivors": rows, "medians": medians}


HOST_SPANS = ("prod.", "ring.", "svc.")


def gaps_by_host_span(xplane: str, top: int = 10) -> list:
    """The sample's longest device idle gaps (between whole executions on
    the `XLA Modules` line, inside the benchmark's window span, as
    trace_reduce's `idle_gaps`), each with the host spans that overlap it:
    [{"gap_ms", "before", "host": {span name: share of the gap}}]."""
    from benchmarks import trace_reduce as tr
    window, mods, host = None, {}, []
    for plane, line, name, start, dur in tr.xplane_events(xplane):
        if plane.startswith(tr.DEVICE_PLANE_PREFIX):
            if line == tr.MODULE_LINE:
                mods.setdefault(plane, []).append((start, start + dur, name))
        elif name == tr.WINDOW_EVENT:
            window = (start, start + dur)
        elif name.startswith(HOST_SPANS):
            host.append((start, start + dur, name))
    if window is None or not mods:
        return []
    lo, hi = window
    gaps = []
    for events in mods.values():
        prev_end = lo
        for start, end, name in sorted(events):
            if start > prev_end:
                gaps.append((start - prev_end, prev_end, start,
                             "before " + name.split("(")[0]))
            prev_end = max(prev_end, end)
        if hi > prev_end:
            gaps.append((hi - prev_end, prev_end, hi,
                         "after the last program"))
    out = []
    for length, g0, g1, what in sorted(gaps, reverse=True)[:top]:
        cover: dict = {}
        for start, end, name in host:
            both = min(end, g1) - max(start, g0)
            if both > 0:
                cover[name] = cover.get(name, 0) + both
        out.append({"gap_ms": round(length / 1e6, 3), "before": what,
                    "host": {n: round(c / length, 4) for n, c in sorted(
                        cover.items(), key=lambda kv: -kv[1])}})
    return out


def keep_xplane(xplane: str, tag: str) -> dict:
    import shutil
    size = os.path.getsize(xplane)
    kept = None
    if size <= 24 << 20:
        out = os.path.join(ROOT, "chiprun_out", "probe_trace")
        os.makedirs(out, exist_ok=True)
        kept = os.path.join(out, tag + ".xplane.pb")
        shutil.copyfile(xplane, kept)
    return {"bytes": size, "kept": kept}


def as_cell() -> int:
    from benchmarks import cell, manifest, readers, trace_reduce
    runs, lanes_seen, windows, infos_seen, waits_seen = [], [], [], [], []
    survivors_seen: list = []
    init, metrics, window = cell.Run.__init__, cell.metrics, cell.Run.window
    collect_trace = cell.Run.collect_trace

    def remember(self, args):
        init(self, args)
        runs.append(self)
        topo = self.topo
        snapshot = topo.snapshot
        infos_of = getattr(topo, "_validator_infos", None)
        if infos_of is not None:        # four owners: no request of our own
            def remember_infos():
                topo.last_infos = infos_of()
                return topo.last_infos
            topo._validator_infos = remember_infos

        def snapshot_and_lanes():
            got = snapshot()
            lanes_seen.append(by_lanes(topo))
            infos_seen.append(validator_infos(topo))
            waits_seen.append(service_waits(topo))
            return got
        topo.snapshot = snapshot_and_lanes
        survivors_of = getattr(topo, "_survivors_view_changes", None)
        if survivors_of is not None:    # the failover cell, after its drain
            def remember_survivors():
                survivors_seen.append(survivors_of())
                return survivors_seen[-1]
            topo._survivors_view_changes = remember_survivors

    def remember_window(self, *args, **kwargs):
        # a window takes its snapshots itself, before and after the drive;
        # set-up may have taken one already (the four owners' does)
        at = len(lanes_seen)
        got = window(self, *args, **kwargs)
        windows.append((lanes_seen[at:at + 2], infos_seen[at:at + 2],
                        waits_seen[at:at + 2]))
        return got

    def trace_and_gaps(self, win, trace_dir):
        collect_trace(self, win, trace_dir)
        xplane = trace_reduce.find_xplane(trace_dir)
        cell.say(probe_gaps=gaps_by_host_span(xplane),
                 probe_xplane=keep_xplane(
                     xplane, f"{self.args.workload}.{self.args.seed}"))

    def say_layers(name, group, obs):
        layers = {}
        for m in manifest.metrics_of(name, "per_layer"):
            try:
                layers[m["name"]] = readers.read(
                    manifest.metric_spec("per_layer", m["name"]), obs)
            except Exception as e:      # a reader that wants the trace
                layers[m["name"]] = f"not read: {e!r}"
        lanes, every, waits = windows[0] if windows else ([], [], [])
        # the validator the cell reads from, and all of them by name
        infos = [next(iter(seen.values()), {}) for seen in every]
        names = list(every[0]) if every else []
        cell.say(probe_per_layer=layers, probe_cuts=cut_counts(runs[0].topo),
                 probe_lanes=window_lanes(lanes),
                 probe_stages=window_stages(
                     [info.get("stages") for info in infos], runs[0].topo,
                     obs["numbers"]),
                 probe_transport=window_transport(
                     [info.get("transport") for info in infos]),
                 probe_round=window_round(infos, obs["numbers"]),
                 probe_bls={name: window_bls(
                     [seen.get(name, {}).get("bls") for seen in every])
                     for name in names},
                 probe_failover_steps=failover_steps(survivors_seen[-1])
                 if survivors_seen else None,
                 probe_propagation={name: window_propagation(
                     [seen.get(name, {}).get("propagation")
                      for seen in every]) for name in names},
                 probe_service_waits=dict(
                     grown(waits) or {}, since_pin=waits[1]) if len(
                     waits) > 1 and waits[1] else None)
        return metrics(name, group, obs)

    cell.Run.__init__ = remember
    cell.Run.window = remember_window
    cell.Run.collect_trace = trace_and_gaps
    cell.metrics = say_layers
    return cell.main()


if __name__ == "__main__":
    if "--run-dir" in sys.argv:
        sys.exit(as_cell())
    from benchmarks import run
    sys.exit(run.main(cell_script=os.path.abspath(__file__)))
