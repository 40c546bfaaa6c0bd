"""Kernel microbenchmarks on the attached TPU.

Measures Ed25519 verify-kernel v3 sigs/s at the headline batch sizes
(2048 warm, 128 small-dispatch), and the batch SHA-256 Merkle leaf
kernel. Replaces the hot spot the reference spends its CPU on
(/root/reference/stp_core/crypto/nacl_wrappers.py:62,212 — scalar
libsodium verify per request per node).

Run: python -m plenum_tpu.tools.tpu_microbench [--batches 2048,128]
Prints one JSON line per measurement plus a trailing summary line.
Whether there is a chip is decided by asking JAX: on anything but a TPU
the tool fails before measuring (a CPU figure is not a device figure).
"""
from __future__ import annotations

import json
import sys
import time


def _now_iso() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def bench_ed25519(batch: int, reps: int = 5) -> dict:
    """sigs/s for one warm fixed-shape dispatch of `batch` signatures."""
    import numpy as np
    from plenum_tpu.crypto.ed25519 import Ed25519Signer, JaxEd25519Verifier

    rng = np.random.default_rng(7)
    signers = [Ed25519Signer(bytes(rng.integers(0, 256, 32, dtype=np.uint8)))
               for _ in range(min(batch, 64))]
    items = []
    for i in range(batch):
        s = signers[i % len(signers)]
        msg = bytes(rng.integers(0, 256, 64, dtype=np.uint8))
        items.append((msg, s.sign(msg), s.verkey))
    ver = JaxEd25519Verifier(min_batch=batch)
    # warm: compile + verkey-cache fill
    t0 = time.perf_counter()
    out = ver.verify_batch(items)
    compile_s = time.perf_counter() - t0
    if not bool(out.all()):
        return {"error": f"verdicts wrong at batch {batch}"}
    # negative control: one corrupted signature must flip exactly one verdict
    bad = list(items)
    bad[0] = (bad[0][0], bad[0][1][:32] + bytes(32), bad[0][2])
    out_bad = ver.verify_batch(bad)
    if bool(out_bad[0]) or not bool(out_bad[1:].all()):
        return {"error": f"negative control failed at batch {batch}"}
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        ver.verify_batch(items)
        times.append(time.perf_counter() - t0)
    best = min(times)
    med = sorted(times)[len(times) // 2]
    return {
        "kernel": "ed25519_verify_v3", "batch": batch,
        "compile_plus_first_s": round(compile_s, 3),
        "warm_best_s": round(best, 5), "warm_median_s": round(med, 5),
        "sigs_per_s_best": round(batch / best, 1),
        "sigs_per_s_median": round(batch / med, 1),
        "reps": reps,
    }


def bench_sha256(batch: int = 4096, reps: int = 5) -> dict:
    """Merkle leaf-hash kernel: batch SHA-256 over 64-byte blocks."""
    import numpy as np
    try:
        from plenum_tpu.ops import sha256 as s256
    except Exception as e:  # pragma: no cover
        return {"error": f"sha256 ops import: {e}"}
    rng = np.random.default_rng(3)
    leaves = [bytes(rng.integers(0, 256, 48, dtype=np.uint8))
              for _ in range(batch)]
    import hashlib
    t0 = time.perf_counter()
    out = s256.sha256_batch(leaves, prefix=b"\x00")   # RFC 6962 leaf prefix
    compile_s = time.perf_counter() - t0
    ref0 = hashlib.sha256(b"\x00" + leaves[0]).digest()
    got0 = out[0] if isinstance(out[0], bytes) else bytes(np.asarray(out)[0])
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        s256.sha256_batch(leaves, prefix=b"\x00")
        times.append(time.perf_counter() - t0)
    best = min(times)
    return {
        "kernel": "sha256_leaves", "batch": batch,
        "compile_plus_first_s": round(compile_s, 3),
        "warm_best_s": round(best, 5),
        "hashes_per_s_best": round(batch / best, 1),
        "leaf0_matches_hashlib": got0 == ref0,
        "reps": reps,
    }


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="2048,128")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    from plenum_tpu.ops import device_info
    header = {"ts": _now_iso(), "device": device_info()}
    print(json.dumps(header), flush=True)
    if header["device"]["platform"] != "tpu":
        print(json.dumps({"error": "no TPU: JAX found "
                                   f"{header['device']}"}))
        return 1

    results = []
    for b in [int(x) for x in args.batches.split(",") if x]:
        r = bench_ed25519(b, reps=args.reps)
        r["ts"] = _now_iso()
        print(json.dumps(r), flush=True)
        results.append(r)
    r = bench_sha256(reps=args.reps)
    r["ts"] = _now_iso()
    print(json.dumps(r), flush=True)
    results.append(r)

    errors = [r["error"] for r in results if "error" in r]
    summary = {"summary": True, **header, "errors": errors,
               "ed25519": {str(r["batch"]): r.get("sigs_per_s_best")
                           for r in results if r.get("kernel") == "ed25519_verify_v3"}}
    print(json.dumps(summary), flush=True)
    # rc mirrors correctness: a wrong verdict / failed negative control
    # must not look like a passed device run to log-scrapers
    return 2 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
