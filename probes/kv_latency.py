#!/usr/bin/env python3
"""What one row costs the native store on the filesystem a benchmark run's
data directories lie on (tempfile's directory, as benchmarks/run.py takes
it), and on /dev/shm beside it for scale: a lone put (one fflush), a
64-row scope (one fflush), a get of an old row (fseek + fread through the
persistent handle). Host numbers; no JAX, no device.

    chiprun -- python3 probes/kv_latency.py [rows]"""
import json
import os
import random
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.topologies.tcp_durable import fs_type  # noqa: E402
from plenum_tpu.storage.kv_native import KvNative  # noqa: E402


def per_call_us(fn, n: int) -> float:
    t0 = time.perf_counter()
    for i in range(n):
        fn(i)
    return (time.perf_counter() - t0) / n * 1e6


def measure(base: str, rows: int) -> dict:
    path = tempfile.mkdtemp(prefix="kv_latency_", dir=base)
    try:
        kv = KvNative(path)
        value = os.urandom(160)
        with kv.write_batch():
            for i in range(rows):
                kv.put(i.to_bytes(8, "big"), value)
        rng = random.Random(7)
        keys = [rng.randrange(rows).to_bytes(8, "big") for _ in range(20000)]
        out = {"filesystem": fs_type(path), "rows": rows,
               "get_us": per_call_us(lambda i: kv.get(keys[i]), len(keys)),
               "lone_put_us": per_call_us(
                   lambda i: kv.put(b"p%d" % i, value), 5000)}

        def scope(i):
            with kv.write_batch():
                for j in range(64):
                    kv.put(b"s%d/%d" % (i, j), value)
        out["scope_of_64_us"] = per_call_us(scope, 200)
        kv.close()
        return out
    finally:
        shutil.rmtree(path, ignore_errors=True)


if __name__ == "__main__":
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 60000
    for base in (tempfile.gettempdir(), "/dev/shm"):
        if os.path.isdir(base):
            print(json.dumps({"kv_latency": base, **measure(base, rows)}),
                  flush=True)
