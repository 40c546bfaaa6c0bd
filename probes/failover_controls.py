"""Builder's probe: the two controls of `tcp_failover.primary_kill` through
the benchmark's own entry, on the chip or as a rehearsal. Both have to
come out `correct: false`:

    --control no_kill       the same run with the kill left out: no view
                            change happened
    --control non_primary   the kill aimed at the last validator, which
                            is no primary in view 0: no view change is owed

    python3 probes/failover_controls.py --control no_kill \
        --workload tcp_failover.primary_kill --seed <n> --seconds 20 --trace 0

This file stands in for benchmarks/cell.py (run.py's `cell_script` seam)
and patches the launcher's two seams, as tests/benchmark/
test_tcp_failover_cpu.py does; nothing of the benchmark is changed and the
last line is run.py's own."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def as_cell() -> int:
    from benchmarks import cell
    from benchmarks.topologies import tcp_failover
    argv = sys.argv[1:]
    at = argv.index("--control")
    control = argv[at + 1]
    del argv[at:at + 2]
    if control == "no_kill":
        tcp_failover.Launcher.kill_victim = lambda self: None
    elif control == "non_primary":
        tcp_failover.Launcher.pick_victim = \
            lambda self, primary: self.names[-1]
    else:
        raise SystemExit(f"no control {control!r}")
    print('{"control": "%s"}' % control, flush=True)
    return cell.main(argv)


if __name__ == "__main__":
    if "--run-dir" in sys.argv:
        sys.exit(as_cell())
    from benchmarks import run
    sys.exit(run.main(cell_script=os.path.abspath(__file__)))
