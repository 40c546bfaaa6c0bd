"""The benchmark's entry to the crypto service: the chip's owner process.

Runs plenum_tpu.parallel.crypto_service.main unchanged, beside one control
thread that lets the benchmark (a) hold a jax.profiler trace in THIS
process, the only one that can trace the chip, and (b) read the device and
its peak memory as JAX reports them here. Commands are files in --ctl:
the benchmark writes `<cmd>`, the thread answers with `<cmd>.done`.

    trace    body: "<log directory>\n<seconds>"  -> trace_reduce.hold_trace
    report                                       -> {"device", "memory_peak_bytes"}

The program has no profiler hook (PERF.md, Open questions: the tracing
issue moves this into parallel/crypto_service.py)."""
from __future__ import annotations

import json
import os
import sys
import threading
import time


def _answer(path: str, body: dict) -> None:
    with open(path + ".tmp", "w") as fh:
        json.dump(body, fh)
    os.replace(path + ".tmp", path + ".done")


def control_loop(ctl: str) -> None:
    while True:
        time.sleep(0.01)
        for cmd in ("trace", "report"):
            path = os.path.join(ctl, cmd)
            if not os.path.exists(path):
                continue
            with open(path) as fh:
                arg = fh.read().strip()
            os.unlink(path)
            try:
                if cmd == "trace":
                    from benchmarks.trace_reduce import hold_trace
                    log_dir, seconds = arg.split("\n")
                    body = hold_trace(log_dir, float(seconds))
                else:
                    from benchmarks.topologies.base import device_report
                    body = device_report()
            except Exception as e:      # the benchmark reads the error
                body = {"error": f"{type(e).__name__}: {e}"}
            _answer(path, body)


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    ctl = argv[argv.index("--ctl") + 1]
    del argv[argv.index("--ctl"):argv.index("--ctl") + 2]
    threading.Thread(target=control_loop, args=(ctl,), daemon=True).start()
    from plenum_tpu.parallel import crypto_service
    crypto_service.main(argv)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
