"""Builder's probe: one benchmark cell run that also says, on an earlier
line, what an untraced run can already count.

A `--trace 0` run of the benchmark reports only end-to-end metrics, and a
traced run costs five minutes of writing the trace out. This stand-in for
`benchmarks/cell.py` runs the same cell and, just before the result, prints
`probe_per_layer` (every per-layer metric of the cell whose reader finds its
counters or samples without a trace) and `probe_cuts` (the first
validator's cut-reason counts, `consensus.batch_cut_*`: flushed store when
the nodes are processes, the node's controller when they are in process;
null on a checkout that has no such counter). Nothing of the benchmark is
changed: the last line is run.py's own.

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


def as_cell() -> int:
    from benchmarks import cell, manifest, readers
    runs = []
    init, metrics = cell.Run.__init__, cell.metrics

    def remember(self, args):
        init(self, args)
        runs.append(self)

    def say_layers(name, group, obs):
        layers = {}
        for m in manifest.metrics_of(name, "per_layer"):
            try:
                layers[m["name"]] = readers.read(
                    manifest.metric_spec("per_layer", m["name"]), obs)
            except Exception as e:      # a reader that wants the trace
                layers[m["name"]] = f"not read: {e!r}"
        cell.say(probe_per_layer=layers, probe_cuts=cut_counts(runs[0].topo))
        return metrics(name, group, obs)

    cell.Run.__init__ = remember
    cell.metrics = say_layers
    return cell.main()


if __name__ == "__main__":
    if "--run-dir" in sys.argv:
        sys.exit(as_cell())
    from benchmarks import run
    sys.exit(run.main(cell_script=os.path.abspath(__file__)))
