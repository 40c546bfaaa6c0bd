"""Cross-process crypto plane: one device owner, many node clients.

Why this exists (measured, round 4): (a) a chip belongs to one process at
a time — four OS-process nodes each initializing their own jax backend
wedge on device contention (tcp_pool backend=jax ordered 0 txns), so
the device needs ONE owner process; (b) every client request
is signature-verified by all n co-hosted nodes (the propagate path,
ref plenum/server/client_authn.py:273 runs on every node), which the
7-node scaling analysis (docs/performance.md) names as part of the
dominant cost — a host-wide verdict cache collapses those n
verifications into one.

Design: an asyncio unix-socket server fronting a single inner
`Ed25519Verifier` (cpu | jax | jax-sharded via the existing factory
seam). A worker thread drains a queue of client batches: everything
that arrives while the previous device dispatch runs is coalesced into
the next one, which is also the backpressure. Verdicts are
cached by content digest (bounded FIFO), so a request already verified
for node A is free for nodes B..N.

Wire: 4-byte big-endian length frames, msgpack maps.
  request  {"id": u64, "items": [[msg, sig, vk], ...]}
  reply    {"id": u64, "verdicts": [0|1, ...]}
  request  {"op": "stats"} -> server counters (ops tooling; among them
           "dispatches_by_lanes": {"64": n, "512": m}, the device
           dispatches by the padded lane count of the program that ran
           each), the device the owner process runs on as JAX reports it
           ("device": {platform, kind, count}; null for the cpu backend)
           and the process's compile counters ("compile": plenum_tpu.ops
           .compile_stats()).
  request  {"id": u64, "items": [...], "wave": 1} -> verdicts; the batch
           dispatches VERBATIM as its own wave (no dedup/coalescing, pad
           items preserved) so a federated lane's pinned bucket is
           exactly the shape the remote inner sees (parallel/federation.py).
  request  {"id": u64, "op": "prewarm", "buckets": [...],
            "full_keys": 0|1} -> {"id", "warmed", "bucketed"}: compile
           the pad buckets now; bucketed says whether the inner is
           device-backed (a host inner would verify pad lanes for real,
           so the lane ships bare waves). full_keys additionally warms
           each bucket's FULL key-table shape (a wave of all-distinct
           verkeys) — what a plain client's coalesced waves dispatch
           once more than 64 signers share one. A warm wave the device
           did not answer (raised, hedged) is an error reply. A
           device-backed inner whose min_batch is above the small bucket
           (64 lanes) also obtains and proves the small program, in the
           same preload: the inner pads each wave to the smallest program
           it holds, so short waves run there and not in min_batch lanes.
           "warmed" names only buckets that were asked for.
  request  {"id": u64, "op": "pin"} -> {"id", "pinned"}: warmup over.

Server:  python -m plenum_tpu.parallel.crypto_service --socket PATH \
             [--backend cpu|jax|jax-sharded] [--min-batch N]
Client:  make_verifier("service") with PLENUM_CRYPTO_SOCKET set, or
         ServiceEd25519Verifier(path) directly.
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import json
import os
import queue
import socket
import struct
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np

from plenum_tpu.common.metrics import Accumulator, span_report
from plenum_tpu.common.serialization import pack, unpack
from plenum_tpu.crypto.ed25519 import (SMALL_LANES, Ed25519Verifier,
                                       VerifyItem)

_LEN = struct.Struct(">I")
MAX_FRAME = 64 * 1024 * 1024
DEFAULT_SOCKET = "/tmp/plenum_crypto.sock"
CACHE_SIZE = 65536


# one shared length-prefixed digest for every verdict cache — the
# anti-aliasing property is load-bearing (see content_digest docstring)
from plenum_tpu.crypto.ed25519 import content_digest as _digest


class CryptoPlaneServer:
    """Owns the inner verifier; coalesces client batches in a worker
    thread so the asyncio loop never blocks on a device dispatch."""

    def __init__(self, inner: Ed25519Verifier,
                 socket_path: str = DEFAULT_SOCKET,
                 cache_size: int = CACHE_SIZE,
                 device: Optional[dict] = None):
        self._inner = inner
        # the device this owner process runs its inner on, as JAX
        # reports it (plenum_tpu.ops.device_info); None = host verifier
        self.device = device
        # BLS aggregate checks ride the same plane: each co-hosted node
        # runs the IDENTICAL per-batch pairing (~4 ms), and the
        # process-wide verdict cache inside BlsCryptoVerifier collapses
        # the n-fold repetition automatically once they all ask here
        from plenum_tpu.crypto.bls import BlsCryptoVerifier
        self._bls = BlsCryptoVerifier()
        # single-flight: key -> future, so n co-hosted nodes submitting
        # the identical order-time check inside one pairing window run
        # ONE pairing, not n (the Ed25519 path gets this from the
        # worker's coalescing todo map; BLS bypasses the queue)
        self._bls_pending: dict = {}
        self.socket_path = socket_path
        self._q: "queue.Queue" = queue.Queue()
        # content-digest -> bool; FIFO-bounded like the verkey cache
        # (attacker-supplied keys must not grow it without bound)
        self._cache: dict[bytes, bool] = {}
        self._cache_size = cache_size
        self.stats = {"batches": 0, "items": 0, "cache_hits": 0,
                      "dispatches": 0, "dispatched_items": 0}
        # padded lane count -> device dispatches the program of that size
        # ran (host verdicts have no lanes); the worker writes, stats reads
        self._by_lanes: collections.Counter = collections.Counter()
        # how long one node's batch waits HERE, in two parts: put on the
        # queue -> taken by the worker, and taken -> its verdicts handed
        # back (a pure cache hit: 0; a job riding a wave: until it lands).
        # The worker adds, `stats` reads, `pin` starts them afresh
        self._waits = self._new_waits()
        self._server = None
        self._worker: Optional[threading.Thread] = None
        self._stop = threading.Event()

    @staticmethod
    def _new_waits() -> dict:
        return {"queue": Accumulator(keep_samples=True, seed=1),
                "wave": Accumulator(keep_samples=True, seed=2)}

    def _waits_report(self) -> dict:
        # list(): one step under the GIL, the worker may add meanwhile
        return {name: span_report(acc.count, acc.total, list(acc.samples))
                for name, acc in self._waits.items()}

    # --- worker thread: the only place the inner verifier runs ----------

    def _plane_fault(self, counter: str) -> None:
        """EVERY swallowed worker-loop error lands here: a named counter
        (ops can tell collect stalls from submit failures from cycle
        bugs), the legacy aggregate, and — when the inner verifier is
        supervised — a breaker feed, so repeated device faults open the
        circuit even for error paths the supervisor itself never saw."""
        self.stats[counter] = self.stats.get(counter, 0) + 1
        self.stats["errors"] = self.stats.get("errors", 0) + 1
        breaker = getattr(self._inner, "breaker", None)
        if breaker is not None:
            try:
                breaker.record_failure()
            except Exception:
                pass

    def _bucketed(self) -> bool:
        """Is the inner chain device-backed? Federated lanes pad their
        waves only when the answer is yes — a host inner would verify
        every pad lane for real (the same rule as CryptoPipeline's own
        `_bucketed`, answered server-side during prewarm negotiation)."""
        from plenum_tpu.parallel.pipeline import _device_backed
        return _device_backed(self._inner)

    def _small_bucket(self) -> Optional[int]:
        """Lanes of the small verify program every prewarm holds beside
        the buckets it is asked for, or None: a device inner pads a wave
        to the smallest program it holds (`_pad_sizes`), and one started
        with a large min_batch would hold nothing else and run a wave of
        nine signatures in 512 lanes. A host inner pads nothing, and a
        min_batch at or under the small bucket is small enough."""
        from plenum_tpu.parallel.pipeline import _device_verifier
        dev = _device_verifier(self._inner)
        if dev is None or dev.min_batch <= SMALL_LANES:
            return None
        return SMALL_LANES

    def _drain(self, first) -> list:
        jobs = [first]
        while True:
            try:
                jobs.append(self._q.get_nowait())
            except queue.Empty:
                return jobs

    # Up to 2 dispatch waves in flight: while wave k computes on the
    # device, the worker drains the queue and STAGES wave k+1 (per-item
    # sha512 + byte packing happen inside submit_batch), so host prep
    # overlaps device compute instead of serializing behind it (the
    # "double-buffer" lever: staging time is time the device spends
    # idle).
    # Cross-wave dedup is preserved: a digest already computing in an
    # in-flight wave is WAITED ON (the job attaches to that wave), never
    # re-dispatched, so the co-hosted n-nodes-same-content case still
    # costs one device verification.
    _MAX_IN_FLIGHT = 2

    def _worker_loop(self) -> None:
        waves: "collections.deque" = collections.deque()  # in flight, FIFO
        pending: dict[bytes, int] = {}   # digest -> seq computing it
        recent: dict[int, object] = {}   # landed seq -> verdicts | error str
        next_seq = 1

        def _finish(done, plan, taken=None):
            """Resolve one job from its plan: ('v', verdict) snapshots and
            ('w', seq, digest) waits settled by landed waves. A wait on a
            wave that is NOT in `recent` as a verdict dict (errored, or —
            submit-failure path only — not yet landed) resolves the whole
            job as an error: the job referenced a failed dispatch.
            `taken`: when the worker took the job off the queue (None: it
            waited for no wave)."""
            self.stats["batches"] += 1
            self._waits["wave"].add(
                0.0 if taken is None else time.perf_counter() - taken)
            out, err = [], None
            for entry in plan:
                if entry[0] == "v":
                    out.append(entry[1])
                    continue
                r = recent.get(entry[1])
                if not isinstance(r, dict):
                    err = r if isinstance(r, str) else \
                        "dispatch failed before dependency landed"
                    break
                out.append(r[entry[2]])
            try:
                done(err if err is not None else out)
            except Exception:
                # loop closing mid-shutdown: nothing to notify — but NEVER
                # silently (a growing counter here means live clients are
                # not receiving verdicts, which is a plane fault)
                self.stats["notify_failures"] = \
                    self.stats.get("notify_failures", 0) + 1

        def _land(block: bool) -> bool:
            """Try to retire the oldest in-flight wave. -> landed?"""
            wave = waves[0]
            # host phases go onto the profiler's clock while a trace is
            # held (a TraceMe: an atomic load otherwise)
            with jax.profiler.TraceAnnotation(
                    "svc.land", seq=wave["seq"],
                    lanes=getattr(wave["token"], "lanes", None) or 0):
                return _land_wave(wave, block)

        def _land_wave(wave, block: bool) -> bool:
            try:
                verdicts = self._inner.collect_batch(wave["token"],
                                                     wait=block)
            except Exception as e:
                # backend/device failure (e.g. the runtime dying
                # mid-dispatch) must surface as an ERROR to every waiting
                # client, not kill this thread — a dead worker would
                # silently wedge every co-hosted node
                verdicts = f"{type(e).__name__}: {e}"
            if verdicts is None:
                return False
            waves.popleft()
            if isinstance(verdicts, str):
                self._plane_fault("collect_errors")
                recent[wave["seq"]] = verdicts
            else:
                self.stats["dispatches"] += 1
                lanes = getattr(wave["token"], "lanes", None)
                if lanes is not None:
                    self._by_lanes[lanes] += 1
                # wave frames dispatch verbatim (pads included), so their
                # honest width is the batch, not the distinct digests
                self.stats["dispatched_items"] += wave.get(
                    "width", len(wave["todo"]))
                new = {d: bool(verdicts[i])
                       for d, i in wave["todo"].items()}
                recent[wave["seq"]] = new
                self._cache.update(new)
            for d in wave["todo"]:
                if pending.get(d) == wave["seq"]:
                    del pending[d]
            for job in wave["jobs"]:
                _finish(*job)
            # a job attaches to the LAST wave it references, and references
            # only waves in flight at its intake (>= seq - _MAX_IN_FLIGHT):
            # anything 4 seqs back can no longer be referenced
            for s in [s for s in recent if s <= wave["seq"] - 4]:
                del recent[s]
            if len(self._cache) > self._cache_size:
                # FIFO eviction in bulk; dict preserves insert order
                drop = len(self._cache) - self._cache_size
                for k in list(self._cache)[:drop]:
                    del self._cache[k]
            return True

        def _dispatch_raw(done, batch, digests, taken) -> None:
            """One wave-frame job: the batch dispatches VERBATIM as its
            own wave — no dedup, no coalescing, pad items preserved — so
            the shape the inner sees is exactly the bucket the federated
            lane packed (its pinned-ladder guarantee crosses the wire
            intact). Verdicts still land in the shared digest cache."""
            nonlocal next_seq
            seq = next_seq
            next_seq += 1
            self.stats["wave_frames"] = self.stats.get("wave_frames", 0) + 1
            self.stats["items"] += len(batch)
            todo: dict[bytes, int] = {}
            plan: list = []
            for i, d in enumerate(digests):
                if d not in todo:
                    todo[d] = i
                plan.append(("w", seq, d))
            try:
                with jax.profiler.TraceAnnotation("svc.submit", seq=seq):
                    token = self._inner.submit_batch(batch)
            except Exception as e:
                recent[seq] = f"{type(e).__name__}: {e}"
                self._plane_fault("submit_errors")
                _finish(done, plan, taken)
                for s in [s for s in recent if s <= seq - 4]:
                    del recent[s]
                return
            if waves:
                self.stats["overlapped"] = self.stats.get(
                    "overlapped", 0) + 1
            waves.append({"seq": seq, "token": token, "todo": todo,
                          "width": len(batch),
                          "jobs": [(done, plan, taken)]})
            while len(waves) > self._MAX_IN_FLIGHT:
                _land(block=True)

        def _cycle() -> None:
            while waves and _land(block=False):
                pass
            nonlocal next_seq
            with jax.profiler.TraceAnnotation("svc.drain"):
                try:
                    first = self._q.get(timeout=0.2 if not waves else 0.002)
                except queue.Empty:
                    return
                jobs = self._drain(first)   # coalesce everything queued
                taken = time.perf_counter()
                for j in jobs:
                    self._waits["queue"].add(taken - j[4])
                    if j[3]:
                        _dispatch_raw(j[0], j[1], j[2], taken)
                jobs = [j for j in jobs if not j[3]]
                if not jobs:
                    return
                seq = next_seq
                todo: dict[bytes, int] = {}
                items: list[VerifyItem] = []
                wave_jobs: list = []
                for done, batch, digests, _, _ in jobs:
                    self.stats["items"] += len(batch)
                    plan: list = []
                    dep = 0
                    for it, d in zip(batch, digests):
                        hit = self._cache.get(d)
                        if hit is not None:
                            self.stats["cache_hits"] += 1
                            plan.append(("v", hit))
                            continue
                        w = pending.get(d)
                        if w is None:
                            if d not in todo:
                                todo[d] = len(items)
                                items.append(it)
                                pending[d] = seq
                            w = seq
                        plan.append(("w", w, d))
                        dep = max(dep, w)
                    if dep == 0:
                        _finish(done, plan)        # pure cache hit
                    elif dep == seq:
                        wave_jobs.append((done, plan, taken))
                    else:
                        for w in waves:            # ride an in-flight wave
                            if w["seq"] == dep:
                                w["jobs"].append((done, plan, taken))
                                break
            if not items:
                return
            next_seq += 1
            try:
                with jax.profiler.TraceAnnotation("svc.submit", seq=seq):
                    token = self._inner.submit_batch(items)
            except Exception as e:
                recent[seq] = f"{type(e).__name__}: {e}"
                self._plane_fault("submit_errors")
                for d in todo:
                    if pending.get(d) == seq:
                        del pending[d]
                for job in wave_jobs:
                    _finish(*job)
                # prune here too: with a persistently broken backend _land
                # never runs, and one error entry per failed dispatch must
                # not grow `recent` without bound in the shared service
                for s in [s for s in recent if s <= seq - 4]:
                    del recent[s]
                return
            if waves:
                self.stats["overlapped"] = self.stats.get(
                    "overlapped", 0) + 1
            waves.append({"seq": seq, "token": token, "todo": todo,
                          "jobs": wave_jobs})
            while len(waves) > self._MAX_IN_FLIGHT:
                _land(block=True)

        while not self._stop.is_set():
            try:
                _cycle()
            except Exception:
                # LAST-RESORT guard: a bug anywhere in the cycle must not
                # kill this thread — a dead worker silently wedges every
                # co-hosted node. Named counter + breaker feed (never a
                # bare swallow); the cycle's wave state is self-healing
                # (jobs of a wave that never lands resolve as errors when
                # it is pruned, and clients fall back locally on error
                # replies).
                self._plane_fault("worker_faults")

    # --- asyncio front end ----------------------------------------------

    async def _bls_check(self, loop, sig, msg, vks) -> bool:
        from plenum_tpu.crypto import bls as bls_mod
        sig, msg = str(sig), bytes(msg)
        vks = [str(v) for v in vks]
        key = bls_mod._bls_verdict_key(b"multi", sig.encode(), msg,
                                       *sorted(v.encode() for v in vks))
        hit = bls_mod._BLS_VERDICTS.get(key)
        if hit is not None:
            return hit
        pending = self._bls_pending.get(key)
        if pending is not None:
            # shield: a cancelled waiter must not cancel the shared future
            # out from under every other waiter
            kind, val = await asyncio.shield(pending)
            if kind == "err":
                raise RuntimeError(val)
            return val
        fut = loop.create_future()
        self._bls_pending[key] = fut
        # The pairing runs detached from THIS request: if the submitting
        # client disconnects mid-pairing (its _process task is cancelled),
        # the done-callback below still pops the key and resolves `fut`,
        # so every other waiter on this single-flight entry gets the real
        # verdict instead of awaiting a dead future forever.
        work = asyncio.ensure_future(loop.run_in_executor(
            None, self._bls.verify_multi_sig, sig, msg, vks))

        def _settle(t, key=key, fut=fut):
            self._bls_pending.pop(key, None)
            if fut.done():
                return
            exc = t.exception()
            if exc is not None:
                fut.set_result(("err", f"{type(exc).__name__}: {exc}"))
            else:
                self.stats["bls_pairings"] = (
                    self.stats.get("bls_pairings", 0) + 1)
                fut.set_result(("ok", t.result()))

        work.add_done_callback(_settle)
        # shield: cancelling this waiter must not cancel the shared fut
        kind, val = await asyncio.shield(fut)
        if kind == "err":
            raise RuntimeError(val)
        return val

    async def _process(self, req: dict, writer, wlock) -> None:
        """One request end-to-end; runs as its own task so a connection's
        pipelined batches overlap (submit B2 while B1 is on the device)
        instead of serializing behind each other's replies."""
        loop = asyncio.get_running_loop()

        def _resolve(fut, result):
            if not fut.cancelled():     # disconnect may cancel us first
                fut.set_result(result)

        rid = None
        try:
            if req.get("op") == "stats":
                from plenum_tpu.ops import compile_stats
                # dict() of a dict is one step under the GIL: the worker
                # may add a lane count meanwhile
                by_lanes = sorted(dict(self._by_lanes).items())
                out = dict(self.stats, cache_size=len(self._cache),
                           dispatches_by_lanes={str(k): v
                                                for k, v in by_lanes},
                           waits=self._waits_report(),
                           device=self.device, compile=compile_stats())
                sup = getattr(self._inner, "supervisor_stats", None)
                if callable(sup):
                    # breaker state / fallbacks / hedge wins of the
                    # supervised device plane, readable over the socket
                    out["plane"] = sup()
                payload = pack(out)
            elif req.get("op") == "prewarm":
                # federated-lane ladder negotiation: compile each pad
                # bucket NOW with one verbatim all-pad wave (the raw path
                # bypasses dedup, so the dispatched shape IS the bucket).
                # Sequential per bucket — simultaneous enqueues would
                # coalesce in _drain and shrink the compiled shape.
                rid = req["id"]
                warmed: list = []
                payload = None
                from plenum_tpu.parallel.pipeline import PREWARM_ITEM
                from plenum_tpu.parallel.supervisor import (
                    fallback_growth, find_supervisor)
                sup = find_supervisor(self._inner)
                waves = []
                buckets = [int(x) for x in req.get("buckets", []) if x]
                for b in buckets:
                    waves.append((b, [PREWARM_ITEM] * b))
                    if req.get("full_keys"):
                        # b distinct (junk) verkeys: past 64 of them the
                        # inner stages the bucket's full key table
                        waves.append((b, [
                            (*PREWARM_ITEM[:2], i.to_bytes(32, "little"))
                            for i in range(b)]))
                small = self._small_bucket()
                if small is not None and small not in buckets:
                    waves.append((small, [PREWARM_ITEM] * small))
                # every wave's program first: the executable store loads
                # what this machine compiled before, the rest compile at
                # once. ON THIS THREAD, the loop's and the process's main
                # one, and not in an executor: a PjRt load takes ~10 s
                # from here and 50-75 s from any other thread (PR 26), and
                # nothing else is served before pin. A program that cannot
                # be obtained is this request's error; the waves below
                # then prove each one answers.
                self._inner.preload(
                    [(len(items), len({vk for _, _, vk in items}))
                     for _, items in waves])
                for b, items in waves:
                    digests = [_digest(*it) for it in items]
                    before = sup.supervisor_stats() if sup else None
                    fut = loop.create_future()
                    self._q.put((lambda result, f=fut:
                                 loop.call_soon_threadsafe(_resolve, f,
                                                           result),
                                 items, digests, True, time.perf_counter()))
                    result = await fut
                    if not isinstance(result, str) and sup is not None:
                        # the supervised inner answers a failed device
                        # dispatch from the CPU; in warm-up that would
                        # report a bucket compiled that never compiled
                        grew = fallback_growth(before,
                                               sup.supervisor_stats())
                        if grew:
                            result = f"not answered by the device: {grew}"
                    if isinstance(result, str):    # compile/dispatch died
                        payload = pack({"id": rid, "error":
                                        f"prewarm bucket {b}: {result}"})
                        break
                    if b in buckets and b not in warmed:
                        warmed.append(b)
                if payload is None:
                    self.stats["prewarms"] = \
                        self.stats.get("prewarms", 0) + 1
                    payload = pack({"id": rid, "warmed": warmed,
                                    "bucketed": self._bucketed()})
            elif req.get("op") == "pin":
                rid = req["id"]
                # warmup-over marker; ladder enforcement lives in the
                # federated lane's shape set on the client side
                self.stats["pinned"] = 1
                self._waits = self._new_waits()     # warm-up stays outside
                payload = pack({"id": rid, "pinned": True})
            elif "bls" in req:
                # [[sig_b58, msg_bytes, [verkey_b58...]], ...] -> bools.
                # Pairings run in the default executor (the BN254 ctypes
                # call releases the GIL, so neither the event loop nor
                # the Ed25519 worker stalls); repeated content is served
                # by the process-wide verdict cache, and concurrent
                # identical checks share one pairing via single-flight
                rid = req["id"]
                results = [await self._bls_check(loop, *c)
                           for c in req["bls"]]
                self.stats["bls_checks"] = (
                    self.stats.get("bls_checks", 0) + len(req["bls"]))
                payload = pack({"id": rid,
                                "verdicts": [int(v) for v in results]})
            else:
                rid = req["id"]
                batch = [(bytes(m), bytes(s), bytes(v))
                         for m, s, v in req["items"]]
                digests = [_digest(*it) for it in batch]
                fut = loop.create_future()
                self._q.put((lambda result, f=fut:
                             loop.call_soon_threadsafe(_resolve, f, result),
                             batch, digests, bool(req.get("wave")),
                             time.perf_counter()))
                result = await fut
                if isinstance(result, str):      # backend failure
                    payload = pack({"id": rid, "error": result})
                else:
                    payload = pack({"id": rid,
                                    "verdicts": [int(v) for v in result]})
        except asyncio.CancelledError:
            raise
        except Exception as e:
            # schema garbage: answer THIS request with an error when we
            # know its id; the connection and its other in-flight
            # requests live on. Without an id there is no way to reply —
            # drop the connection so the sender gets ConnectionError
            # instead of blocking forever on a reply that can't come.
            if rid is None:
                writer.close()
                return
            payload = pack({"id": rid, "error": f"bad request: {e}"})
        try:
            async with wlock:
                writer.write(_LEN.pack(len(payload)) + payload)
                await writer.drain()
        except Exception:
            # dead writer: drop the connection — counted, a rising rate
            # means clients are dying mid-reply (node/socket trouble)
            self.stats["dead_writers"] = self.stats.get("dead_writers", 0) + 1
            writer.close()

    async def _handle(self, reader, writer) -> None:
        wlock = asyncio.Lock()
        tasks: set = set()
        try:
            while True:
                hdr = await reader.readexactly(4)
                length = _LEN.unpack(hdr)[0]
                if length > MAX_FRAME:
                    return
                req = unpack(await reader.readexactly(length))
                t = asyncio.create_task(self._process(req, writer, wlock))
                tasks.add(t)
                t.add_done_callback(tasks.discard)
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        except Exception:
            # malformed frame (bad msgpack, wrong schema): drop THIS
            # connection; the plane itself must survive garbage clients —
            # counted so a flood of garbage is visible in the stats op
            self.stats["bad_connections"] = \
                self.stats.get("bad_connections", 0) + 1
        finally:
            for t in tasks:
                t.cancel()
            writer.close()

    async def start(self) -> None:
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)
        self._worker = threading.Thread(target=self._worker_loop,
                                        daemon=True)
        self._worker.start()
        # owner-only FROM CREATION (umask, not post-hoc chmod — a chmod
        # after listen leaves a connect window): any local user reaching
        # the socket could churn the verdict cache and monopolize the
        # single shared device
        old_umask = os.umask(0o177)
        try:
            self._server = await asyncio.start_unix_server(
                self._handle, path=self.socket_path)
        finally:
            os.umask(old_umask)

    async def stop(self) -> None:
        self._stop.set()
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)


class ServiceEd25519Verifier(Ed25519Verifier):
    """Client side of the plane: ships batches to the owner process over
    a unix socket. Implements the same submit/collect token protocol as
    the in-process verifiers, so node pipelining works unchanged.

    Thread-safety: one socket, one lock; replies are matched by id so
    multiple outstanding submits are fine."""

    def __init__(self, socket_path: Optional[str] = None,
                 connect_timeout: float = 5.0,
                 request_timeout: float = 300.0,
                 warm_timeout: float = 30.0):
        self.socket_path = socket_path or os.environ.get(
            "PLENUM_CRYPTO_SOCKET", DEFAULT_SOCKET)
        self._connect_timeout = connect_timeout
        # PER-REQUEST deadline budget (replaces the old flat 300 s recv
        # timeout, which made a wedged device cost 5 minutes PER BATCH):
        # deadline = base + n_items * rolling-p99 per-item cost, clamped.
        # request_timeout survives as the COLD ceiling — the first
        # dispatch on a fresh service may sit behind a multi-minute XLA
        # compile — and warm_timeout caps every budget after the first
        # success, so a mid-run wedge costs one bounded miss.
        from plenum_tpu.parallel.supervisor import DeadlineBudget
        self._request_timeout = request_timeout
        self._budget = DeadlineBudget(base=2.0, per_item_initial=0.01,
                                      margin=8.0, min_s=1.0,
                                      warm_max=warm_timeout,
                                      cold_max=request_timeout)
        self._lock = threading.Lock()
        self._next_id = 0
        self._replies: dict[int, list] = {}
        # rid -> (t0, n, deadline): the deadline is FIXED at submit time —
        # a cold request that was promised the compile ceiling must not be
        # re-judged by the warmed (shorter) budget at collect time
        self._meta: dict[int, tuple[float, int, float]] = {}
        self._discarded: set[int] = set()
        # partial frame bytes survive across non-blocking polls — throwing
        # them away on BlockingIOError would desync the framing forever
        self._rxbuf = b""
        self._connect()                        # fail fast: operator error

    def _connect(self) -> None:
        self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._sock.settimeout(self._connect_timeout)
        self._sock.connect(self.socket_path)
        self._sock.settimeout(self._budget.budget(1))
        self._rxbuf = b""

    def reconnect(self) -> None:
        """Fresh socket to the service; in-flight replies are abandoned
        (their callers see ConnectionError from the closed old socket).
        The plane supervisor calls this as its re-warm step before
        re-admitting the service after an open circuit."""
        with self._lock:
            try:
                self._sock.close()
            except OSError:
                pass
            self._replies.clear()
            self._meta.clear()
            self._discarded.clear()
            self._connect()

    # supervisor re-warm hook: a reconnect IS the client-side re-warm
    # (server-side key caches re-fill on the wire from the next dispatch)
    rewarm = reconnect

    def discard(self, token) -> None:
        """Abandon a request: a reply landing later is dropped instead of
        accumulating forever in the reply map (the supervisor discards
        hedged-and-reaped tokens through this)."""
        rid = token[0]
        with self._lock:
            self._discarded.add(rid)
            self._replies.pop(rid, None)
            self._meta.pop(rid, None)
            if len(self._discarded) > 4096:
                self._discarded.clear()   # ancient rids can't collide soon

    def _deadline_for(self, rid: int) -> float:
        meta = self._meta.get(rid)
        if meta is None:
            return time.monotonic() + self._budget.budget(1)
        return meta[2]

    def _submit_send(self, rid: int, obj, n_items: int) -> None:
        """Register (t0, n, deadline) and send; the meta entry must not
        outlive a failed send (an unsupervised client retrying against a
        down service would otherwise leak one tuple per attempt)."""
        t0 = time.monotonic()
        deadline = t0 + self._budget.budget(n_items)
        self._meta[rid] = (t0, n_items, deadline)
        try:
            self._send(obj, deadline=deadline)
        except Exception:
            self._meta.pop(rid, None)
            raise

    def _send(self, obj, deadline: Optional[float] = None) -> None:
        payload = pack(obj)
        budget = (deadline - time.monotonic()) if deadline is not None \
            else self._budget.budget(1)
        try:
            self._sock.settimeout(max(0.05, budget))
            self._sock.sendall(_LEN.pack(len(payload)) + payload)
        except socket.timeout:
            # a timed-out sendall may have written a PARTIAL frame; the
            # socket's framing is unrecoverable — kill it so every later
            # use fails loudly instead of desyncing the stream
            self._sock.close()
            raise ConnectionError(
                f"crypto service send stalled past its "
                f"{budget:.1f}s budget (socket closed)") from None

    def _parse_frame(self):
        if len(self._rxbuf) < 4:
            return None
        length = _LEN.unpack(self._rxbuf[:4])[0]
        if len(self._rxbuf) < 4 + length:
            return None
        payload = self._rxbuf[4:4 + length]
        self._rxbuf = self._rxbuf[4 + length:]
        return unpack(payload)

    def _recv(self, block: bool = True, deadline: Optional[float] = None):
        """Next complete frame, buffering partial reads. None when
        non-blocking and no complete frame is available yet. Blocking
        reads honor the caller's per-request deadline (adaptive budget,
        not the old flat timeout)."""
        while True:
            frame = self._parse_frame()
            if frame is not None:
                return frame
            if block:
                remaining = (deadline - time.monotonic()
                             if deadline is not None
                             else self._budget.budget(1))
                try:
                    self._sock.settimeout(max(0.05, remaining))
                    chunk = self._sock.recv(65536)
                except socket.timeout:
                    # caller abandons the request; a reply landing later
                    # for a caller that gave up helps nobody — close so
                    # the wedged-service state is unambiguous
                    self._sock.close()
                    raise ConnectionError(
                        f"crypto service unresponsive past its "
                        f"{max(0.05, remaining):.1f}s deadline budget "
                        f"(socket closed)") from None
            else:
                self._sock.setblocking(False)
                try:
                    chunk = self._sock.recv(65536)
                except BlockingIOError:
                    return None
                finally:
                    self._sock.settimeout(self._budget.budget(1))
            if not chunk:
                raise ConnectionError("crypto service closed")
            self._rxbuf += chunk

    def _stash_reply(self, reply: dict) -> None:
        rid = reply.get("id")
        if rid in self._discarded:
            self._discarded.discard(rid)       # abandoned: drop on arrival
            self._meta.pop(rid, None)
            return
        self._replies[rid] = reply

    def submit_batch(self, items: Sequence[VerifyItem]):
        items = [(bytes(m), bytes(s), bytes(v)) for m, s, v in items]
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self._submit_send(rid, {"id": rid, "items": items},
                              max(1, len(items)))
        return (rid, len(items))

    def collect_batch(self, token, wait: bool = True):
        rid, n = token
        with self._lock:
            deadline = self._deadline_for(rid)
            while rid not in self._replies:
                reply = self._recv(block=wait, deadline=deadline)
                if reply is None:
                    return None
                self._stash_reply(reply)
            reply = self._replies.pop(rid)
            meta = self._meta.pop(rid, None)
            if meta is not None and "error" not in reply:
                # successful round-trip: tighten the rolling budget
                self._budget.record(meta[1], time.monotonic() - meta[0])
        if "error" in reply:
            # backend/device failure or a request the server rejected —
            # loud, not a silent all-False verdict (which would read as
            # 'n invalid signatures' and trigger bogus suspicions)
            raise RuntimeError(f"crypto service: {reply['error']}")
        return np.array(reply["verdicts"], dtype=bool)

    def verify_batch(self, items: Sequence[VerifyItem]) -> np.ndarray:
        return self.collect_batch(self.submit_batch(items), wait=True)

    def verify_bls_multi(self, signature: str, message: bytes,
                         verkeys) -> bool:
        """One aggregate check via the plane (the server's process-wide
        verdict cache dedupes identical checks across co-hosted nodes)."""
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self._submit_send(rid, {"id": rid,
                                    "bls": [[signature, bytes(message),
                                             list(verkeys)]]}, 1)
        reply = self.collect_batch((rid, 1), wait=True)
        return bool(reply[0])

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass

    def stats(self) -> dict:
        with self._lock:
            deadline = time.monotonic() + 10.0
            self._send({"op": "stats"}, deadline=deadline)
            while True:
                reply = self._recv(deadline=deadline)
                if "id" in reply:        # verify reply racing ahead of ours
                    self._stash_reply(reply)
                    continue
                return reply


class FederatedEd25519Client(ServiceEd25519Verifier):
    """Remote-lane client of the federated pipeline (parallel/
    federation.py): verify batches ship as WAVE FRAMES (`"wave": 1`) the
    server dispatches verbatim — no server-side dedup or coalescing, so
    the padded bucket the lane packed is EXACTLY the shape the remote
    inner compiles, and the lane's pinned-ladder guarantee crosses the
    wire intact — plus the prewarm/pin RPCs the pipeline negotiates a
    remote host's pad ladder with before pinning."""

    def submit_batch(self, items: Sequence[VerifyItem]):
        items = [(bytes(m), bytes(s), bytes(v)) for m, s, v in items]
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self._submit_send(rid, {"id": rid, "items": items, "wave": 1},
                              max(1, len(items)))
        return (rid, len(items))

    def _rpc(self, req: dict, n_items: int = 1,
             timeout: Optional[float] = None) -> dict:
        """Blocking control round-trip (prewarm/pin): submit and hold
        the lock through the reply — control ops run during warmup only
        and must not interleave with verify replies. `timeout` overrides
        the adaptive per-item budget: a prewarm sits behind the remote's
        XLA compiles, which the item-count formula knows nothing about."""
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self._submit_send(rid, dict(req, id=rid), n_items)
            deadline = (time.monotonic() + timeout if timeout is not None
                        else self._deadline_for(rid))
            while rid not in self._replies:
                reply = self._recv(block=True, deadline=deadline)
                self._stash_reply(reply)
            reply = self._replies.pop(rid)
            self._meta.pop(rid, None)
        if "error" in reply:
            # a remote that cannot compile its ladder must fail warmup
            # LOUDLY (the same contract as the local lane prewarm)
            raise RuntimeError(f"crypto service: {reply['error']}")
        return reply

    def prewarm(self, buckets: Sequence[int],
                full_keys: bool = False) -> dict:
        """Compile the remote's pad buckets NOW. -> {"warmed": [...],
        "bucketed": bool}; bucketed False means the remote inner is a
        host verifier (padding would burn real verifies there), so the
        lane ships bare waves instead. full_keys also warms each
        bucket's full key-table shape (see the wire notes above)."""
        want = sorted({int(b) for b in buckets if int(b) >= 1})
        # the cold ceiling per compile, not the per-item budget: this
        # request IS the multi-minute first-compile the budget's cold_max
        # exists for
        # + 1: a device-backed remote adds its small program's wave
        n_waves = len(want) * (2 if full_keys else 1) + 1
        return self._rpc({"op": "prewarm", "buckets": want,
                          "full_keys": int(full_keys)},
                         n_items=max(1, sum(want)),
                         timeout=self._request_timeout * max(1, n_waves))

    def pin(self) -> dict:
        """Declare warmup over on the remote (stats marker; the lane's
        own compiled-shape set enforces the ladder on this side)."""
        return self._rpc({"op": "pin"})


class ServiceBlsVerifier:
    """BlsCryptoVerifier facade that routes the hot aggregate check to
    the crypto-plane service, consulting the local process-wide verdict
    cache first (repeat checks inside ONE node cost a dict hit, repeat
    checks ACROSS nodes cost one IPC round-trip instead of a 4 ms
    pairing). Everything else (PoP, well-formedness, aggregation)
    delegates to the local implementation, and so does the order-time
    check of a batch's COMMIT signatures (`batch_verify_begin` /
    `batch_verify_end`, consensus/bls_bft_replica.py): a round trip to
    the plane is a wait on a socket, which a node's loop would have to
    sit out, where the local check runs on the BLS library's own thread
    beside the loop. What still crosses is what gates a message at once:
    the multi-signature a PRE-PREPARE carries (`verify_multi_sig`)."""

    def __init__(self, socket_path: Optional[str] = None, breaker=None):
        from plenum_tpu.crypto import bls as _bls
        from plenum_tpu.parallel.supervisor import CircuitBreaker
        self._local = _bls.BlsCryptoVerifier()
        self._bls_mod = _bls
        self._client = ServiceEd25519Verifier(socket_path=socket_path)
        # breaker over the IPC path: a dead plane costs ONE bounded miss
        # per cooldown window, not one socket deadline per aggregate check
        self.breaker = breaker or CircuitBreaker(fail_threshold=3,
                                                 cooldown=5.0)
        self.stats = {"ipc_checks": 0, "local_fallbacks": 0}

    def verify_multi_sig(self, signature: str, message: bytes,
                         verkeys) -> bool:
        verkeys = list(verkeys)
        if not verkeys:
            return False
        b = self._bls_mod
        key = b._bls_verdict_key(b"multi", signature.encode(), message,
                                 *sorted(v.encode() for v in verkeys))
        hit = b._BLS_VERDICTS.get(key)
        if hit is not None:
            return hit
        from plenum_tpu.parallel import supervisor as _sup
        probing = False
        if self.breaker.state != _sup.CLOSED:
            if not self.breaker.probe_due():
                # circuit open: verify locally, instantly
                self.stats["local_fallbacks"] += 1
                return self._local.verify_multi_sig(signature, message,
                                                    verkeys)
            # half-open: this very check doubles as the probe; re-warm
            # (fresh socket) before re-admitting the plane
            probing = True
            self.breaker.to_half_open()
        try:
            if probing:
                self._client.reconnect()
            verdict = self._client.verify_bls_multi(signature, message,
                                                    verkeys)
            self.stats["ipc_checks"] += 1
            if probing:
                self.breaker.close()
            else:
                self.breaker.record_success()
        except (OSError, RuntimeError, ConnectionError):
            # plane down mid-run: verify locally rather than stalling
            # consensus on an ops failure
            if probing:
                self.breaker.reopen()
            else:
                self.breaker.record_failure()
            self.stats["local_fallbacks"] += 1
            return self._local.verify_multi_sig(signature, message, verkeys)
        return b._bls_cache_put(key, verdict)

    def batch_verify(self, items) -> list:
        """COMMIT-set batch verification over the shared plane. When every
        triple signs the SAME message (the commit path always does), the
        deterministic aggregate check is tried first because the service
        dedups it host-wide — co-hosted nodes run the IDENTICAL check, so
        one IPC round-trip settles it for the whole host, where the
        random-coefficient combined check (fresh randomness per node by
        design) never dedups. Any failure, mixed messages, or malformed
        input falls back to the local RLC batch check, whose per-signature
        fallback names the culprit signer(s) individually.

        DELIBERATE trade-off: the aggregate fast path certifies the SET,
        not each signature — an error-cancelling pair (σ₁+δ, σ₂−δ) is
        accepted here (the summed artifact equals the honest aggregate and
        remains a valid multi-sig, so consensus artifacts stay sound) where
        the local RLC path would reject and evict both. Blame precision is
        traded for host-wide dedup ONLY in this opt-in co-hosted plane
        topology; isolated nodes always take the individually-certifying
        path."""
        items = list(items)
        msgs = {m for _, m, _ in items}
        if len(items) > 1 and len(msgs) == 1:
            try:
                agg = self._local.create_multi_sig([s for s, _, _ in items])
            except (ValueError, KeyError):
                return self._local.batch_verify(items)
            if self.verify_multi_sig(agg, next(iter(msgs)),
                                     [v for _, _, v in items]):
                return [True] * len(items)
        return self._local.batch_verify(items)

    def close(self) -> None:
        self._client.close()

    def __getattr__(self, name):
        return getattr(self._local, name)


def make_bls_verifier(backend: str):
    """BLS twin of crypto.ed25519.make_verifier: 'service' routes the
    per-batch aggregate checks through the shared plane; anything else
    verifies locally."""
    if backend == "service":
        return ServiceBlsVerifier()
    from plenum_tpu.crypto.bls import BlsCryptoVerifier
    return BlsCryptoVerifier()


def main(argv=None):

    from plenum_tpu.crypto.ed25519 import make_verifier

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--socket", default=DEFAULT_SOCKET)
    ap.add_argument("--backend", default="cpu",
                    choices=["cpu", "jax", "jax-sharded"])
    ap.add_argument("--min-batch", type=int, default=128)
    ap.add_argument("--no-supervisor", action="store_true",
                    help="run the device verifier bare (no breaker / "
                         "hedged CPU fallback) — debugging only")
    args = ap.parse_args(argv)

    # device backends come supervised from the factory: a wedged device
    # behind this service degrades every client to CPU-speed verdicts
    # instead of erroring (or stalling) each batch
    inner = make_verifier(args.backend, min_batch=args.min_batch,
                          supervised=False if args.no_supervisor else None)
    # a jax backend makes THIS process the chip's owner: ask JAX what it
    # got (JAX itself falls back to the CPU when it finds no accelerator)
    # and say so in the start line and in stats(), so a launcher can
    # refuse a device run that is not on the device
    device = None
    if args.backend.startswith("jax"):
        from plenum_tpu.ops import device_info
        device = device_info()
    server = CryptoPlaneServer(inner, socket_path=args.socket, device=device)

    async def run():
        await server.start()
        print(json.dumps({"crypto_service": args.socket,
                          "backend": args.backend,
                          "device": device,
                          "supervised": hasattr(inner, "supervisor_stats")}),
              flush=True)
        try:
            while True:
                await asyncio.sleep(3600)
        finally:
            await server.stop()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
