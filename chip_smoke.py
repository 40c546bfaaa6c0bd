#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the validator pool's device path
still starts, and still answers correctly, on the attached TPU.

    python3 chip_smoke.py [--seed N]       # on a machine with the chip
    python3 chip_smoke.py --rehearse-cpu   # tiny CPU rehearsal, labelled

Deployment: upstream config 1/2 of BASELINE.json — 4 validators, f=1, BLS
multi-signatures on, Max3PCBatchSize=1000, receive quotas 100/100,
local_pool's Max3PCBatchWait=0.05, the plane supervisor on as in
production. Stream (from --seed): 2 048 trustee-signed NYM writes creating
2 048 DIDs, then 2 048 ATTRIB writes each signed by its own DID (so waves
meet the 64-key table cap), then proof-verified reads of a sample.

The parent never imports JAX. It runs the phases as CHILD processes, one
after another, so exactly one process holds the chip at any time:

  single     kernel check (device vs CPU verdict vectors with corrupted
             signatures at the smallest and the largest bucket and both
             key-table shapes; sha256_batch and merkle_wave vs hashlib),
             then phase A: tools/local_pool's in-process ring pool with
             backend="jax" — every write acknowledged, four nodes agree on
             ledger/state/audit roots, sampled reads verify client-side,
             and the run's transactions extend a fresh ledger through the
             pool's device hasher (Ledger.append_batch) to the same root.
  served     phase B: tools/tcp_pool — four start_node processes over TCP
             with --backend service, one crypto_service --backend jax
             process owning the chip, prewarmed over its RPC; f+1 matching
             replies per write.
  four_chip  only where JAX shows >= 4 chips: phase A's pool with one lane
             per chip (four distinct devices, every lane dispatching,
             per-lane correctness waves) + one ShardedCryptoPlane
             step_bytes on the 2x2 mesh. Otherwise reported "not run".

No fallback may hide the device: over each traffic window device batches
must grow while every fallback / hedge / deadline / error / breaker /
verdict-fork / unpinned-shape / commit-wave-host-fallback counter and the
number of executables obtained stay flat. Set-up (compile + prewarm) is
timed apart from the window, and each phase's set-up line carries
ops.compile_stats(): on a machine whose executable store (ops/aot.py)
already holds a verify shape, tracing that shape again fails the phase.

Exit 0 and a last stdout line {"ok": true, "device": {...}} only when
every phase passed on a TPU. No TPU -> non-zero before any work.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import signal
import subprocess
import sys
import time
from typing import NamedTuple, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_PATH = os.path.join(HERE, "chiprun_out", "chip_smoke.json")
TOTAL_BUDGET_S = 1150.0         # the contract allows 1200, compile included
PHASES = ("single", "served", "four_chip")


class Sizes(NamedTuple):
    writes: int                 # per kind: NYMs, then as many ATTRIBs
    reads: int                  # verified reads sampled from the writes
    kernel_shapes: tuple        # (bucket, distinct keys) verify checks
    leaves: int                 # sha256_batch / merkle_wave check width
    pool_overrides: dict        # Config overrides for the in-process pool
    service_min_batch: int      # crypto_service pad bucket (phase B)
    window: int                 # requests in flight


# Largest first: PIPELINE_MAX_BUCKET=4096 with its full key table (the
# shape that must stay), then the service's bucket at both key tables,
# then the pool's pinned ladder [64, 128] (at bucket 64 the two key-table
# shapes coincide: 64 keys IS the full table). 4096 x 64 keys was dropped
# for the time limit: no phase dispatches it.
REAL = Sizes(writes=2048, reads=64,
             kernel_shapes=((4096, 4096), (512, 512), (512, 64), (128, 64),
                            (64, 64)),
             leaves=4096, pool_overrides={}, service_min_batch=512,
             window=256)
# One verify program in all (bucket 64), windows that cannot coalesce past
# it, and just enough writes that the ledger check crosses the device
# hasher's 1024-leaf threshold.
REHEARSAL = Sizes(writes=520, reads=16, kernel_shapes=((64, 64),),
                  leaves=1024, pool_overrides={"PIPELINE_MAX_BUCKET": 64},
                  service_min_batch=64, window=64)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# --- the checks (pure functions; tests feed them directly) -----------------

def window_failures(label: str, before: list, after: list,
                    must_stay_zero: dict) -> list[str]:
    """The no-fallback rule over one traffic window.

    before/after: per-lane `supervisor_stats()` snapshots (after warm-up +
    pin, and after the traffic). Every lane's device_batches must have
    grown and none of supervisor.FALLBACK_COUNTERS may have.
    must_stay_zero: {name: delta over the window} for the counters that
    live elsewhere (pipeline unpinned_shapes, cmt host_fallbacks,
    executables obtained, service worker errors)."""
    from plenum_tpu.parallel.supervisor import fallback_growth
    problems = []
    if not after or len(before) != len(after):
        problems.append(f"{label}: no supervised device plane to judge")
    for i, (b, a) in enumerate(zip(before, after)):
        lane = a.get("label") or f"lane{i}"
        if a["device_batches"] <= b["device_batches"]:
            problems.append(f"{label}/{lane}: no device batch in the window")
        grew = fallback_growth(b, a)
        if grew:
            problems.append(f"{label}/{lane}: not answered by the device: "
                            f"{grew}")
        if a["breaker_state"] != "closed":
            problems.append(f"{label}/{lane}: breaker {a['breaker_state']}")
    problems += [f"{label}: {name} grew by {delta} in the window"
                 for name, delta in must_stay_zero.items() if delta]
    return problems


def store_failures(label: str, compile_stats: dict, held_before: int,
                   shapes: int) -> list[str]:
    """The executable-store rule over one phase's set-up. `held_before`
    of the phase's `shapes` verify programs were in the store when it
    began; each of those must have been LOADED, so at most the others
    may have been traced, and no entry may have been found damaged."""
    problems = []
    if compile_stats["aot_rejected"]:
        problems.append(f"{label}: {compile_stats['aot_rejected']} store "
                        f"entries rejected and compiled again")
    if compile_stats["traces"] > shapes - held_before:
        problems.append(
            f"{label}: {compile_stats['traces']} verify traces with "
            f"{held_before}/{shapes} shapes already in the store "
            f"({compile_stats['aot_loads']} loaded)")
    return problems


def compile_since(before: dict) -> dict:
    """ops.compile_stats() now, less an earlier snapshot."""
    from plenum_tpu.ops import compile_stats
    return {k: round(v - before[k], 3) for k, v in compile_stats().items()}


def shapes_held(waves, devices=(None,)) -> tuple[int, int]:
    """-> (how many of these verify programs the executable store holds
    already, how many there are), over the given lane devices."""
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    held = [h for d in devices
            for h in JaxEd25519Verifier(device=d).in_store(waves).values()]
    return sum(held), len(held)


def require_device(rehearsal: bool, min_count: int = 1) -> dict:
    """Ask JAX what this process got. This initialises the backend: the
    caller is, from here on, the chip's one owner."""
    from plenum_tpu.ops import device_info
    device = device_info()
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"devices: {device['count']}", flush=True)
    want = "cpu" if rehearsal else "tpu"
    if device["platform"] != want or device["count"] < min_count:
        raise SystemExit(
            f"chip_smoke: need {min_count} x {want}, JAX found {device}")
    return device


def require_native() -> dict:
    """A pool on pure-Python BN254 pairings or a pure-Python state codec
    is a different system; the fallbacks in plenum_tpu.native are silent."""
    from plenum_tpu.native import have_native_bn254
    from plenum_tpu.state import native_codec
    from plenum_tpu.storage.kv_native import native_available
    got = {"bn254": have_native_bn254(), "kv": native_available(),
           "mpt_codec": native_codec.available()}
    missing = [k for k, ok in got.items() if not ok]
    if missing:
        raise SystemExit(f"chip_smoke: native libraries did not load: "
                         f"{missing}")
    return got


# --- kernel check ----------------------------------------------------------

def kernel_check(sizes: Sizes, seed: int) -> tuple[dict, list[str]]:
    """Device verdict vectors against CpuEd25519Verifier's over real
    signatures with corrupted ones, at every verify program the phases
    will dispatch plus the ladder's largest; sha256_batch and merkle_wave
    against hashlib.

    The shapes are obtained in ONE preload() from this, the main thread:
    what the executable store (ops/aot.py) holds is loaded in turn, the
    rest compile at once, one thread each. That is set-up economy, not a
    feature: one cold verify program costs ~35 s of tracing and ~130 s
    of XLA:TPU compilation, five in sequence overrun the time limit, and
    XLA compiles with the GIL released. What is obtained here the ring
    pool's prewarm is handed again in this process, and the crypto
    service of the served phase loads from the store without tracing."""
    from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier, Ed25519Signer,
                                           JaxEd25519Verifier)
    from plenum_tpu.ledger.tree_hasher import fused_wave_levels
    from plenum_tpu.ops.sha256 import sha256_batch
    t_start = time.perf_counter()

    def verify_shape(shape: tuple) -> dict:
        bucket, n_keys = shape
        rng = random.Random(seed * 1_000_003 + bucket * 31 + n_keys)
        signers = [Ed25519Signer(seed=hashlib.sha256(
            b"chip-smoke-%d-key-%d" % (seed, k)).digest())
            for k in range(n_keys)]
        items = []
        for i in range(bucket):
            s = signers[i % n_keys]
            msg = b"chip-smoke %d/%d/%d/%d" % (seed, bucket, n_keys, i)
            items.append((msg, s.sign(msg), s.verkey))
        bad = rng.sample(range(bucket), 6)
        for n, j in enumerate(bad):
            msg, sig, vk = items[j]
            if n % 3 == 0:          # a flipped bit in R
                sig = bytes([sig[0] ^ 1]) + sig[1:]
            elif n % 3 == 1:        # a flipped bit in S
                sig = sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]
            else:                   # a good signature on another message
                msg += b"!"
            items[j] = (msg, sig, vk)
        want = CpuEd25519Verifier().verify_batch(items)
        # through preload(), as a prewarm obtains it: the executable
        # checked here (obtained below, before the loop) is the one the
        # pool's prewarm is handed again
        device = JaxEd25519Verifier(min_batch=bucket)
        device.preload([shape])
        got = device.verify_batch(items)
        row = {"bucket": bucket, "keys": n_keys,
               "done_at_s": round(time.perf_counter() - t_start, 1),
               "rejected": int((~got).sum()), "corrupted": len(bad),
               "equal_to_cpu": bool((got == want).all())}
        say("single", kernel=row)
        return row

    from plenum_tpu.ops import aot, compile_stats
    held, shapes = shapes_held(sizes.kernel_shapes)
    c0 = compile_stats()
    JaxEd25519Verifier().preload(sizes.kernel_shapes)
    rows = [verify_shape(shape) for shape in sizes.kernel_shapes]
    out: dict = {"verify": rows, "store_held": [held, shapes],
                 "compile": compile_since(c0)}
    # which obtains overlapped: seconds from the first one's start
    spans = aot.timeline()
    first = min((r["start"] for r in spans), default=0.0)
    say("single", kernel_check_compile=out["compile"],
        store_held=out["store_held"],
        store_timeline=[[r["what"], r["shapes"][0][0], r["shapes"][2][0],
                         round(r["start"] - first, 1),
                         round(r["end"] - first, 1)] for r in spans])
    problems = [f"verify bucket {r['bucket']} x {r['keys']} keys: device "
                f"verdicts differ from CpuEd25519Verifier" for r in rows
                if not r["equal_to_cpu"] or r["rejected"] != r["corrupted"]]
    problems += store_failures("kernel check", out["compile"], held, shapes)

    n = sizes.leaves
    leaves = [b"chip-smoke-leaf-%d-%d" % (seed, i) * (1 + i % 3)
              for i in range(n)]
    ref = [hashlib.sha256(b"\x00" + leaf).digest() for leaf in leaves]
    sha_ok = sha256_batch(leaves, prefix=b"\x00") == ref
    # one full fused wave with no old boundary: level l forms n / 2^(l+1)
    # parents, each compared with hashlib's RFC 6962 interior hash
    depth = n.bit_length() - 1
    levels = fused_wave_levels(ref, [None] * depth, [0] * depth,
                               [n >> (l + 1) for l in range(depth)])
    wave_ok, cur = len(levels) == depth, ref
    for got_level in levels:
        cur = [hashlib.sha256(b"\x01" + cur[i] + cur[i + 1]).digest()
               for i in range(0, len(cur), 2)]
        wave_ok = wave_ok and got_level == cur
    out["sha256_batch"] = {"leaves": n, "equal_to_hashlib": sha_ok}
    out["merkle_wave"] = {"leaves": n, "levels": depth,
                          "equal_to_hashlib": wave_ok}
    say("single", sha256_batch=out["sha256_batch"],
        merkle_wave=out["merkle_wave"])
    if not sha_ok:
        problems.append("sha256_batch differs from hashlib")
    if not wave_ok:
        problems.append("merkle_wave differs from hashlib")
    return out, problems


# --- the stream ------------------------------------------------------------

def attrib_value(seed: int, i: int) -> str:
    return json.dumps({"endpoint": f"smoke-{seed}-{i}"})


def local_stream(trustee, sizes: Sizes, seed: int):
    """-> (nym requests, attrib requests, user signers) for local_pool."""
    from plenum_tpu.common.request import Request
    from plenum_tpu.execution.txn import ATTRIB
    from plenum_tpu.tools.local_pool import signed_nyms
    # req ids leave room for the warm-up NYM signed_nyms also returns
    nyms, users = signed_nyms(trustee, sizes.writes + 1,
                              tag=b"cs%d-" % seed)
    attribs = []
    for i, u in enumerate(users[:-1]):
        req = Request(u.identifier, 1,
                      {"type": ATTRIB, "dest": u.identifier,
                       "raw": attrib_value(seed, i)})
        req.signature = u.sign_b58(req.signing_bytes())
        attribs.append(req)
    return nyms, attribs, users


def verified_reads(pool, users, sizes: Sizes, seed: int) -> tuple[dict, list]:
    """Sampled GET_NYM / GET_ATTR reads, each sent to ONE node and accepted
    only when its state proof and BLS multi-signature verify client-side
    (reads/client.SimReadDriver) and the proven value is what was written."""
    from plenum_tpu.common.request import Request
    from plenum_tpu.execution.txn import GET_ATTR, GET_NYM
    from plenum_tpu.reads import SimReadDriver
    from plenum_tpu.tools.local_pool import pool_bls_keys
    names, nodes, replies = pool.names, pool.nodes, pool.replies

    def submit(name, req):
        nodes[name].handle_client_message(req.to_dict(), "smoke-reader")

    def collect(name):
        out = [m.result for _, m, c in replies[name]
               if isinstance(m, pool.Reply) and c == "smoke-reader"]
        replies[name].clear()
        return out

    def pump(seconds):
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            pool.prod_all()

    driver = SimReadDriver(submit, collect, pump, names,
                           pool_bls_keys(names), freshness_s=1e9,
                           now=pool.timer.get_current_time)
    rng = random.Random(seed + 1)
    wrong = []
    for n, i in enumerate(rng.sample(range(sizes.writes), sizes.reads)):
        user = users[i]
        if n % 2 == 0:
            op = {"type": GET_NYM, "dest": user.identifier}
            want = user.verkey_b58
        else:
            op = {"type": GET_ATTR, "dest": user.identifier,
                  "attr_name": "endpoint"}
            want = attrib_value(seed, i)
        res = driver.read(Request("smoke-reader", n + 1, op),
                          per_node_s=5.0, step_s=0.001)
        data = (res or {}).get("data")
        got = data.get("verkey") if isinstance(data, dict) else data
        if res is None or got != want:
            wrong.append({"write": i, "type": op["type"], "got": got})
    s = driver.stats.summary()
    out = {"reads": sizes.reads, "verified_single_reply":
           s["single_reply_ok"], "failovers": s["failovers"],
           "fallbacks": s["fallbacks"], "wrong": wrong}
    problems = []
    if wrong or s["single_reply_ok"] != sizes.reads:
        problems.append(f"verified reads: {s['single_reply_ok']}/"
                        f"{sizes.reads} verified from one reply, "
                        f"{len(wrong)} wrong")
    return out, problems


def ledger_through_device_hasher(pool) -> tuple[dict, list]:
    """The run's domain transactions into a FRESH ledger through the
    device hasher a pipelined node's ledgers use
    (CryptoPipeline.tree_hasher(): the ring's SHA lane + the fused Merkle
    wave), via the ledger entry point Ledger.append_batch. The ring is a
    fresh one with the pool's config — the pool's own would answer every
    leaf from its digest cache and never reach the kernel. Two appends:
    the genesis row alone (hashlib, below the threshold), then everything
    the run ordered as one wave starting at an odd index, so the fused
    program pairs an old left-boundary node too. Root must be the pool's."""
    from plenum_tpu.ledger.compact_merkle_tree import CompactMerkleTree
    from plenum_tpu.ledger.ledger import Ledger
    from plenum_tpu.parallel.pipeline import CryptoPipeline
    config = pool.pipeline.config
    ring = CryptoPipeline(config=config, sha_device=True,
                          sha_min_device=config.PIPELINE_SHA_MIN_BATCH)
    src = pool.nodes[pool.names[0]].c.db.get_ledger(pool.domain_ledger_id)
    txns = [t for _, t in src.get_all_txns()]
    fresh = Ledger(CompactMerkleTree(ring.tree_hasher()))
    fresh.append_batch(txns[:1])
    fresh.append_batch(txns[1:])
    dispatched = ring.stats["sha_device_dispatches"]
    out = {"entry_point": "Ledger.append_batch over "
                          "CryptoPipeline.tree_hasher()",
           "txns": len(txns), "sha_device_dispatches": dispatched,
           "root_equals_pool": fresh.root_hash == src.root_hash}
    problems = []
    if not out["root_equals_pool"]:
        problems.append("device-hashed ledger root differs from the pool's")
    if dispatched < 2:          # the leaf batch AND the fused Merkle wave
        problems.append(f"Merkle kernels dispatched {dispatched}x through "
                        f"the ledger, expected leaf batch + fused wave")
    return out, problems


# --- phase A (and its four-lane twin) --------------------------------------

def ring_pool(phase: str, sizes: Sizes, seed: int, lanes: int = 1
              ) -> tuple[dict, list]:
    from plenum_tpu.ops import compile_stats
    from plenum_tpu.tools import local_pool as lp
    problems: list[str] = []
    overrides = dict(sizes.pool_overrides)
    if lanes > 1:
        overrides["PIPELINE_DEVICES"] = lanes
    pool = lp.build_pool(4, "jax", config_overrides=overrides)
    pipe = pool.pipeline
    out: dict = {"lanes": lanes}
    if lanes > 1:
        devices = [str(lane.inner.device) for lane in pipe.lanes]
        out["lane_devices"] = devices
        if len(set(devices)) != lanes:
            # parallel/mesh.lane_roster wraps when chips < lanes: fine for
            # a node, an error for a run that claims one lane per chip
            raise SystemExit(f"chip_smoke: {lanes} lanes on "
                             f"{len(set(devices))} distinct devices")
    nyms, attribs, users = local_stream(pool.trustee, sizes, seed)
    # what warm_pool prewarms, per lane (the store's key holds the
    # device): held by the store already, or just obtained by the kernel
    # check in this process, it may not be traced again
    held, shapes = shapes_held(
        [(b, 1) for b in pipe.buckets[:2]],
        [lane.inner.device for lane in pipe.lanes] if lanes > 1 else (None,))
    c0 = compile_stats()
    warm = lp.warm_pool(pool, nyms.pop(), timeout=600.0)
    out["setup_s"] = warm["setup_s"]
    out["setup_compile"] = compile_since(c0)
    say(phase, pool_setup_s=warm["setup_s"], compile=out["setup_compile"],
        store_held=[held, shapes])
    problems += store_failures(phase, out["setup_compile"], held, shapes)

    if lanes > 1:
        # unique content per lane: the ring's verdict cache is shared, so
        # one item set would settle lanes 1..N-1 from lane 0's verdicts
        # and never reach their chips
        from plenum_tpu.crypto.ed25519 import Ed25519Signer
        signer = Ed25519Signer(seed=b"chip-smoke-lanes".ljust(32, b"\0"))
        for lane in pipe.lanes:
            msgs = [b"lane%d-%d-%d" % (lane.idx, seed, i) for i in range(4)]
            wave = [(m, signer.sign(m), signer.verkey) for m in msgs]
            wave.append((b"forged", wave[0][1], signer.verkey))
            d0 = lane.stats["dispatches"]
            got = pipe.collect_verify(
                pipe.submit_verify(wave, lane=lane.idx), wait=True)
            if list(got) != [True] * 4 + [False] \
                    or lane.stats["dispatches"] <= d0:
                problems.append(f"lane{lane.idx}: correctness wave failed "
                                f"or never reached the chip")

    # ---- the traffic window
    sup0 = [s.supervisor_stats() for s in lp.plane_supervisors(pool.plane)]
    pipe0, c0 = dict(pipe.stats), compile_stats()
    acked, seconds = 0, 0.0
    for stage in (nyms, attribs):
        first_reply, _, dt = lp.drive(pool, stage, window=sizes.window,
                                      timeout=400.0)
        acked += len(first_reply)
        seconds += dt
        if len(first_reply) < len(stage):
            break
    sup1 = [s.supervisor_stats() for s in lp.plane_supervisors(pool.plane)]
    c1 = compile_stats()
    out["window"] = {
        "writes_acknowledged": acked, "writes_requested": 2 * sizes.writes,
        "seconds": round(seconds, 3),
        "device_batches": sum(a["device_batches"] - b["device_batches"]
                              for b, a in zip(sup0, sup1)),
        "executables": c1["executables"] - c0["executables"],
        "ed_dispatches": pipe.stats["dispatches"] - pipe0["dispatches"],
        "overflow_waves": pipe.stats["overflow_waves"]
        - pipe0["overflow_waves"],
    }
    if lanes > 1:
        out["window"]["lane_dispatches"] = [
            lane.stats["dispatches"] for lane in pipe.lanes]
        if not all(out["window"]["lane_dispatches"]):
            problems.append("a lane never dispatched")
    if acked != 2 * sizes.writes:
        problems.append(f"{acked}/{2 * sizes.writes} writes acknowledged")
    problems += window_failures(phase, sup0, sup1, {
        "executables obtained": out["window"]["executables"],
        "pipeline unpinned_shapes": pipe.stats["unpinned_shapes"]
        - pipe0["unpinned_shapes"],
        "cmt host_fallbacks": pipe.stats["cmt_host_fallbacks"]
        - pipe0["cmt_host_fallbacks"]})
    out["backend_state"] = lp.plane_report(
        pool.plane, at_pin=warm["supervisors"]).get("backend_state")
    if out["backend_state"] != "ok":
        problems.append(f"backend_state {out['backend_state']}")
    say(phase, window=out["window"], backend_state=out["backend_state"])

    out["roots"] = lp.pool_roots(pool)
    if not out["roots"]["agree"]:
        problems.append("nodes disagree on ledger/state/audit roots")
    out["reads"], p = verified_reads(pool, users, sizes, seed)
    problems += p
    out["ledger"], p = ledger_through_device_hasher(pool)
    problems += p
    say(phase, roots_agree=out["roots"]["agree"], reads=out["reads"],
        ledger=out["ledger"])
    close = getattr(pipe, "close", None)
    if callable(close):
        close()                     # lane worker threads
    return out, problems


def phase_single(sizes: Sizes, seed: int, rehearsal: bool) -> dict:
    t0 = time.perf_counter()
    device = require_device(rehearsal)
    say("single", device=device)
    out: dict = {"device": device, "native": require_native()}
    out["kernel_check"], problems = kernel_check(sizes, seed)
    out["kernel_check"]["seconds"] = round(time.perf_counter() - t0, 1)
    out["pool"], p = ring_pool("single", sizes, seed)
    # everything before the traffic window: backend init, native build,
    # tracing + compiling (kernel check), warm-up txn, prewarm, pin
    out["setup_s"] = round(out["kernel_check"]["seconds"]
                           + out["pool"]["setup_s"], 1)
    return {**out, "problems": problems + p}


def phase_four_chip(sizes: Sizes, seed: int, rehearsal: bool) -> dict:
    device = require_device(rehearsal, min_count=4)
    say("four_chip", device=device)
    out: dict = {"device": device}
    # every chip compiles its own executables, so the ladder is cut to its
    # one smallest bucket here: what this phase proves is placement and
    # per-chip correctness, which one bucket shows as well as two
    sizes = sizes._replace(pool_overrides={**sizes.pool_overrides,
                                           "PIPELINE_MAX_BUCKET": 64})
    out["pool"], problems = ring_pool("four_chip", sizes, seed, lanes=4)
    out["setup_s"] = out["pool"]["setup_s"]
    # the SPMD plane on the same four chips: one step_bytes over the 2x2
    # mesh, verdict vector (one forged signature) and psum count checked
    sys.path.insert(0, HERE)
    from __graft_entry__ import dryrun_multichip
    try:
        dryrun_multichip(4)
        out["spmd_step_bytes"] = "ok"
    except Exception as e:          # reported as the phase's failure
        out["spmd_step_bytes"] = f"{type(e).__name__}: {e}"
        problems.append(f"ShardedCryptoPlane.step_bytes: "
                        f"{out['spmd_step_bytes']}")
    return {**out, "problems": problems}


# --- phase B ---------------------------------------------------------------

def phase_served(sizes: Sizes, seed: int, rehearsal: bool) -> dict:
    """This child is the launcher: it never queries a device. The
    crypto_service process it starts is the chip's one owner."""
    from plenum_tpu.client.wallet import Wallet
    from plenum_tpu.execution.txn import ATTRIB, NYM
    from plenum_tpu.tools.tcp_pool import TRUSTEE_SEED, run_tcp_pool
    wallet = Wallet("chip-smoke")
    trustee = wallet.add_identifier(seed=TRUSTEE_SEED)
    nyms, attribs = [], []
    for i in range(sizes.writes):
        did = wallet.add_identifier(seed=hashlib.sha256(
            b"chip-smoke-%d-did-%d" % (seed, i)).digest())
        nyms.append(wallet.sign_request(
            {"type": NYM, "dest": did, "verkey": wallet.verkey_of(did)},
            identifier=trustee))
        attribs.append(wallet.sign_request(
            {"type": ATTRIB, "dest": did, "raw": attrib_value(seed, i)},
            identifier=did))
    res = run_tcp_pool(n_nodes=4, backend="service:jax",
                       stages=[nyms, attribs], window=sizes.window,
                       service_min_batch=sizes.service_min_batch,
                       timeout=400.0)
    service, final = res.get("service") or {}, res.get("crypto_service") or {}
    device = service.get("device")
    at_pin = service.get("at_pin") or {}
    say("served", device=device, setup_s=service.get("setup_s"),
        compile=at_pin.get("compile"))
    problems = []
    want = "cpu" if rehearsal else "tpu"
    if not device or device["platform"] != want:
        problems.append(f"crypto_service reports device {device}, "
                        f"need platform {want}")
    if res["txns_ordered"] != 2 * sizes.writes:
        problems.append(f"{res['txns_ordered']}/{2 * sizes.writes} writes "
                        f"got f+1 matching replies")
    if at_pin.get("compile"):
        # this launcher cannot ask the store (only the chip's owner can):
        # what the service did not store it must have loaded, untraced
        c = at_pin["compile"]
        shapes = c["aot_stores"] + c["aot_loads"]
        problems += store_failures("served", c, c["aot_loads"], shapes)
        if not shapes:
            problems.append("served: the service's prewarm obtained "
                            "nothing through the executable store")

    def grew(*path) -> int:
        """Growth of one crypto_service stats() counter over the window."""
        a, b = at_pin, final
        for key in path:
            a, b = (a or {}).get(key), (b or {}).get(key)
        return (b or 0) - (a or 0)

    if "plane" not in at_pin or "plane" not in final:
        problems.append("crypto_service reported no supervised plane")
    else:
        problems += window_failures(
            "served", [at_pin["plane"]], [final["plane"]], {
                "executables obtained": grew("compile", "executables"),
                "service worker errors": grew("errors")})
    # the nodes' own supervisors (around their service clients): a node
    # that gave up on the plane and verified on its CPU shows here
    node_side = res.get("crypto_plane") or {}
    for k in ("crypto_fallback_batches", "crypto_hedge_wins",
              "crypto_deadline_misses", "crypto_breaker_opens"):
        if node_side.get(k):
            problems.append(f"served/node: {k} = {node_side[k]}")
    if res.get("backend_state") != "ok":
        problems.append(f"served: node backend_state "
                        f"{res.get('backend_state')}")
    out = {"device": device, "setup_s": service.get("setup_s"),
           "setup_compile": at_pin.get("compile"),
           "window": {"writes_acknowledged": res["txns_ordered"],
                      "writes_requested": 2 * sizes.writes,
                      "seconds": res["seconds"],
                      "device_batches": grew("plane", "device_batches"),
                      "executables": grew("compile", "executables"),
                      "service_dispatches": grew("dispatches")},
           "node_backend_state": res.get("backend_state"),
           "problems": problems}
    say("served", window=out["window"])
    return out


CHILD_PHASES = {"single": phase_single, "served": phase_served,
                "four_chip": phase_four_chip}


def run_phase(name: str, seed: int, rehearsal: bool) -> int:
    """Child entry: run one phase in THIS process, print its result as the
    last stdout line. Exit 0 only when the phase found no problem."""
    sizes = REHEARSAL if rehearsal else REAL
    t0 = time.perf_counter()
    res = CHILD_PHASES[name](sizes, seed, rehearsal)
    res = {"phase": name, "ok": not res["problems"],
           "seconds": round(time.perf_counter() - t0, 1), **res}
    print(json.dumps(res), flush=True)
    return 0 if res["ok"] else 1


# --- the parent ------------------------------------------------------------

def spawn_phase(name: str, seed: int, rehearsal: bool,
                deadline: float) -> dict:
    """Run one phase as a child in its own process group; relay what it
    prints; -> its result line, or a failure record. The whole group is
    killed at the deadline or when the child ends, so nothing it started
    (nodes, the crypto service) outlives it."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--seed", str(seed)] + (["--rehearse-cpu"] if rehearsal else [])
    env = dict(os.environ)
    if rehearsal or name == "served":
        # the served launcher needs no device (tcp_pool hands the chip to
        # the crypto service alone); a rehearsal is held to the CPU
        env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    last: Optional[dict] = None

    def on_alarm(_signum, _frame):
        raise TimeoutError

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(max(1, int(deadline - time.monotonic())))
    try:
        for line in proc.stdout:
            print(f"[{name}] {line.rstrip()}", flush=True)
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(parsed, dict) and "ok" in parsed \
                    and parsed.get("phase") == name:
                last = parsed
        rc = proc.wait()
    except TimeoutError:
        rc, last = None, None
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    if rc is None:
        return {"phase": name, "ok": False,
                "problems": [f"{name}: killed at the time limit"]}
    if last is None or rc != 0:
        problems = (last or {}).get("problems") or \
            [f"{name}: child exited {rc} without a result"]
        return {**(last or {"phase": name}), "ok": False,
                "problems": problems}
    return last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal of the whole script; its "
                         "output is labelled and proves nothing about "
                         "the chip")
    ap.add_argument("--phase", choices=PHASES, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return run_phase(args.phase, args.seed, args.rehearse_cpu)

    deadline = time.monotonic() + TOTAL_BUDGET_S
    results: dict = {}
    device = None
    for name in PHASES:
        if name == "four_chip" and device["count"] < 4:
            results[name] = {"phase": name, "ran": False,
                             "why": f"not run: {device['count']} device(s) "
                                    f"visible, four needed"}
            print(f"[{name}] {results[name]['why']}", flush=True)
            continue
        res = results[name] = spawn_phase(name, args.seed,
                                          args.rehearse_cpu, deadline)
        if name == "single":
            device = res.get("device")
        if not res["ok"]:
            break           # later phases would only burn the time limit
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as fh:
        json.dump({"seed": args.seed, "rehearsal": args.rehearse_cpu,
                   "results": results}, fh, indent=1)
    failed = [p for r in results.values() for p in r.get("problems", ())]
    served = (results.get("served") or {}).get("setup_compile") or {}
    if results["single"]["ok"] and (served.get("aot_stores")
                                    or served.get("traces")):
        # the service's shapes are among the kernel check's: minutes
        # after `single` stored them, another process must load them
        failed.append(f"served: the service traced or stored a verify "
                      f"shape the single phase had just stored: {served}")
    if failed or len(results) != len(PHASES):
        print("chip_smoke FAILED:\n  " + "\n  ".join(failed), flush=True)
        return 1
    for name, res in results.items():
        # smoke output, not a benchmark: it says the path ran, and where
        # the set-up seconds went
        print(f"[summary] {name}: " + (res["why"] if "why" in res else
              json.dumps({"setup_s": res.get("setup_s"),
                          "phase_s": res.get("seconds"),
                          "window": (res.get("pool") or res)["window"]})),
              flush=True)
    summary = {"ok": True, "device": device}
    if args.rehearse_cpu:
        summary["rehearsal"] = "cpu: proves nothing about the chip"
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
