#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmarks/run.py --workload <cell> --seed 1 --seconds 3 --trace 0 --rehearse-cpu

This parent never imports JAX. It starts exactly one cell process
(benchmarks/cell.py) in a session of its own; every process of the run
carries PLENUM_BENCH_RUN=<marker>. On every way out (result written,
failure, the deadline, SIGTERM or SIGINT) it ends the process group, waits
until each member is gone, scans /proc for the marker, and only when the
scan is empty prints the last line. A process the scan had to find makes
the run `correct: false`. Exit 0 only with a result; no TPU, no result."""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import uuid

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import reaper  # noqa: E402

DEADLINE_S = 1150.0     # a first run compiles; the contract allows 1200 s


class Stop(Exception):
    pass


def main(argv=None, cell_script: str | None = None,
         marker: str | None = None,
         term_wait_s: float = reaper.TERM_WAIT_S) -> int:
    """cell_script, marker and term_wait_s are the tests' seams: a stand-in
    cell that leaks on purpose, a marker the test can scan for afterwards,
    and a shorter wait between SIGTERM and SIGKILL."""
    t0 = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; labelled, and writes "
                         "nothing under a metric's name")
    args, passthrough = ap.parse_known_args(argv)

    for needed in ("plenum_tpu", "BENCHMARK.json"):
        if not os.path.exists(os.path.join(ROOT, needed)):
            print(f"benchmark: {needed} is not in this checkout",
                  file=sys.stderr)
            return 2
    reaper.become_subreaper()
    marker = marker or uuid.uuid4().hex
    run_dir = tempfile.mkdtemp(prefix="plenum_bench_")
    result_path = os.path.join(run_dir, "result.json")
    env = dict(os.environ, PYTHONPATH=ROOT)
    env[reaper.MARKER_VAR] = marker
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, cell_script or os.path.join(HERE, "cell.py"),
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--run-dir", run_dir, "--result", result_path,
           "--t0", repr(t0)] + passthrough \
        + (["--rehearse-cpu"] if args.rehearse_cpu else [])

    def on_signal(signum, _frame):
        raise Stop(f"signal {signum}")

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, on_signal)
    proc, why, rc = None, None, None
    try:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                                start_new_session=True)
        rc = proc.wait(timeout=DEADLINE_S)
    except subprocess.TimeoutExpired:
        why = f"the cell process passed the {DEADLINE_S:.0f} s deadline"
    except Stop as e:
        why = str(e)
    finally:
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, signal.SIG_IGN)     # finish the reaping
        left = reaper.reap(marker, proc.pid if proc is not None else None,
                           term_wait_s)
    if left["group_stragglers"] and why is None:
        print(json.dumps({"reaped_from_group": left["group_stragglers"]}),
              flush=True)
    result = None
    if why is None and rc == 0 and os.path.exists(result_path):
        with open(result_path) as fh:
            result = json.load(fh)
    shutil.rmtree(run_dir, ignore_errors=True)
    if result is None or not result.get("device"):
        print(f"benchmark: no result ({why or f'cell process exited {rc}'})",
              file=sys.stderr)
        return 1
    if left["strays"]:
        print(json.dumps({"compared": {
            "check": "processes.found_by_scan", "got": len(left["strays"]),
            "limit": 0, "ok": False, "note": f"pids {left['strays']}"}}),
            flush=True)
        result["correct"] = False
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
