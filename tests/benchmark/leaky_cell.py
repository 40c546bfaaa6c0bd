"""A stand-in for benchmarks/cell.py that leaks on purpose (test_reaping).

It starts the real `tcp_service` launcher at a tiny size with the
`cpu` service inner (four start_node processes and the crypto service),
plus one child that ignores SIGTERM, and then ends in the way --scenario
says, WITHOUT stopping anything: run.py's parent has to reap it all."""
import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import manifest  # noqa: E402
from benchmarks.topologies import tcp_service  # noqa: E402
from benchmarks.topologies.base import Split  # noqa: E402

DEAF = ("import signal, time; signal.signal(signal.SIGTERM, signal.SIG_IGN);"
        " time.sleep(600)")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", required=True)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--result", required=True)
    ap.add_argument("--ready", required=True)
    args, _ = ap.parse_known_args()
    config = manifest.cell("tcp_service.write_steady")["config"]
    config = dict(config, rehearsal_sizes={"preload_dids": 32})
    launcher = tcp_service.Launcher(config, args.run_dir, 1, rehearse=True)
    launcher.start(Split())
    subprocess.Popen([sys.executable, "-c", DEAF])      # same group, deaf
    if args.scenario == "stray":
        subprocess.Popen([sys.executable, "-c", DEAF],
                         start_new_session=True)        # escapes the group
    with open(args.ready, "w") as fh:
        fh.write("up")
    if args.scenario == "exception":
        raise RuntimeError("the cell process fails after it has children")
    if args.scenario == "hang":
        time.sleep(600)
    with open(args.result, "w") as fh:
        json.dump({"correct": True, "attempted": 1, "failed": 0,
                   "metrics": {}, "device": {"platform": "cpu"}}, fh)
    return 0            # leaves four nodes, the service and the deaf child


if __name__ == "__main__":
    sys.exit(main())
