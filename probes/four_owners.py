#!/usr/bin/env python3
"""Step 0 of ISSUE 27: can four processes each own one chip of a four-chip
host?

    chiprun --chips 4 -- python3 probes/four_owners.py

The parent never imports JAX. It starts four children at once, child i
bound to chip i by the `chip_binding` of
benchmarks/configs/pool4_lane_per_chip.json (the environment that worked;
ALLOW_MULTIPLE_LIBTPU_LOAD and per-process ports were tried beside it and
changed nothing: PERF.md, "Step 0"), and asks each for `jax.devices()` and
one tiny program. If each child sees exactly one TPU and the four hold four
distinct device files (all four alive at once), stage 2: every child runs
the (64, 64) verify program through JaxEd25519Verifier (preload through the
executable store, then one wave) and compares the verdict vector with
CpuEd25519Verifier's.

The last stdout line is one JSON object: {"ok", "env"};
details land in chiprun_out/four_owners.json."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "chiprun_out", "four_owners.json")
N = 4


def chip_env(i: int) -> dict:
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "pool4_lane_per_chip.json")
    with open(path) as fh:
        binding = json.load(fh)["chip_binding"]
    return {key: value.replace("<i>", str(i))
            for key, value in binding.items()}


def device_files() -> list:
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            link = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if link.startswith("/dev/") and not link.startswith((
                "/dev/null", "/dev/pts", "/dev/tty", "/dev/urandom",
                "/dev/random", "/dev/shm", "/dev/zero")):
            held.add(link)
    return sorted(held)


def child(stage: str) -> None:
    sys.path.insert(0, ROOT)
    t0 = time.time()
    import jax
    devs = jax.devices()
    out = {"pid": os.getpid(), "stage": stage,
           "devices": [{"platform": d.platform, "kind": d.device_kind,
                        "id": d.id, "coords": getattr(d, "coords", None),
                        "core_on_chip": getattr(d, "core_on_chip", None)}
                       for d in devs],
           "backend_s": round(time.time() - t0, 2)}
    import jax.numpy as jnp
    out["tiny"] = int(jax.jit(lambda x: (x * 2).sum())(jnp.arange(8)))
    # with TPU_PROCESS_BOUNDS=1,1,1 every process calls its chip id 0 at
    # coords (0, 0, 0); which chip it is shows in the device file it holds
    out["device_files"] = device_files()
    if stage == "verify":
        import numpy as np
        from plenum_tpu import ops
        from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier,
                                               Ed25519Signer,
                                               JaxEd25519Verifier)
        from plenum_tpu.ops import aot
        out["cache_dir"] = jax.config.jax_compilation_cache_dir
        store = aot.store_dir()
        out["store_entries"] = sorted(os.listdir(store)) \
            if store and os.path.isdir(store) else []
        signers = [Ed25519Signer(seed=bytes([k + 1]) * 32)
                   for k in range(40)]
        items = []
        for k in range(56):
            s = signers[k % len(signers)]
            msg = b"four-owners %d" % k
            items.append((msg, s.sign(msg), s.verkey))
        for k in range(8):              # corrupted copies
            msg, sig, vk = items[k]
            items.append((msg, bytes([sig[0] ^ 1]) + sig[1:], vk)
                         if k % 2 else (msg + b"!", sig, vk))
        jv = JaxEd25519Verifier(min_batch=64)
        out["in_store"] = {str(k): v
                           for k, v in jv.in_store([(64, 40)]).items()}
        t1 = time.time()
        jv.preload([(64, 40)])
        out["preload_s"] = round(time.time() - t1, 2)
        t2 = time.time()
        got = np.asarray(jv.verify_batch(items), dtype=bool)
        out["wave_s"] = round(time.time() - t2, 3)
        want = np.asarray(CpuEd25519Verifier().verify_batch(items),
                          dtype=bool)
        out["verdicts_equal"] = bool((got == want).all())
        out["accepted"] = int(got.sum())
        out["compile"] = ops.compile_stats()
        stats = devs[0].memory_stats() or {}
        out["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
    print("CHILD " + json.dumps(out), flush=True)


def run_stage(stage: str, timeout: float) -> list:
    procs = []
    for i in range(N):
        env = dict(os.environ, PYTHONPATH=ROOT, **chip_env(i))
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", stage],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    deadline = time.monotonic() + timeout
    results = []
    for i, p in enumerate(procs):
        try:
            so, se = p.communicate(timeout=max(1.0,
                                               deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            so, se = p.communicate()
            results.append({"chip": i, "rc": "timeout",
                            "stderr": se[-1500:]})
            continue
        line = next((ln[6:] for ln in so.splitlines()
                     if ln.startswith("CHILD ")), None)
        results.append({"chip": i, "rc": p.returncode,
                        "out": json.loads(line) if line else None,
                        "stderr": "" if line else se[-1500:]})
    return results


def distinct_single_tpus(results: list) -> bool:
    """Each child alone on one TPU, and no chip's file held by two. (All
    four were alive at once, so a chip with one owner at a time cannot
    have served two of them; where no device file shows, that is the
    evidence there is.)"""
    held = []
    for r in results:
        devs = (r.get("out") or {}).get("devices") or []
        if r["rc"] != 0 or len(devs) != 1 or devs[0]["platform"] != "tpu":
            return False
        # the chip's own file; /dev/vfio/vfio is the shared container
        held.append({f for f in r["out"]["device_files"]
                     if f != "/dev/vfio/vfio"})
    return len(set().union(*held)) == sum(map(len, held))


def main() -> int:
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        child(sys.argv[2])
        return 0
    t0 = time.time()
    devices = run_stage("devices", 120.0)
    report = {"devices": {"seconds": round(time.time() - t0, 1),
                          "children": devices}}
    print(json.dumps(report["devices"]), flush=True)
    ok = distinct_single_tpus(devices)
    if ok:
        t0 = time.time()
        verify = run_stage("verify", 700.0)
        report["verify"] = {"seconds": round(time.time() - t0, 1),
                            "children": verify}
        print(json.dumps(report["verify"]), flush=True)
        ok = all(r["rc"] == 0 and (r["out"] or {}).get("verdicts_equal")
                 for r in verify)
    report.update(ok=ok, env=chip_env(0))
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "env": report["env"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
