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
with the smallest program's share; null on a checkout older than PR 38).
Nothing of the benchmark is changed: the last line is run.py's own.

    python3 probes/cell_layers.py --workload <cell> --seed <n> --seconds 20
"""
from __future__ import annotations

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


def as_cell() -> int:
    from benchmarks import cell, manifest, readers
    runs, lanes_seen, windows = [], [], []
    init, metrics, window = cell.Run.__init__, cell.metrics, cell.Run.window

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
            return got
        topo.snapshot = snapshot_and_lanes

    def remember_window(self, *args, **kwargs):
        # a window takes its snapshots itself, before and after the drive;
        # set-up may have taken one already (the four owners' does)
        at = len(lanes_seen)
        got = window(self, *args, **kwargs)
        windows.append(lanes_seen[at:at + 2])
        return got

    def say_layers(name, group, obs):
        layers = {}
        for m in manifest.metrics_of(name, "per_layer"):
            try:
                layers[m["name"]] = readers.read(
                    manifest.metric_spec("per_layer", m["name"]), obs)
            except Exception as e:      # a reader that wants the trace
                layers[m["name"]] = f"not read: {e!r}"
        cell.say(probe_per_layer=layers, probe_cuts=cut_counts(runs[0].topo),
                 probe_lanes=window_lanes(windows[0] if windows else []))
        return metrics(name, group, obs)

    cell.Run.__init__ = remember
    cell.Run.window = remember_window
    cell.metrics = say_layers
    return cell.main()


if __name__ == "__main__":
    if "--run-dir" in sys.argv:
        sys.exit(as_cell())
    from benchmarks import run
    sys.exit(run.main(cell_script=os.path.abspath(__file__)))
