"""Cross-process crypto plane (parallel/crypto_service.py): one device
owner, many clients; coalescing, verdict cache, and the OS-process pool
topology it exists for."""
from __future__ import annotations

import asyncio
import contextlib
import os
import threading

import numpy as np
import pytest


def _make_items(n, signers=4, tag=b""):
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    sgs = [Ed25519Signer((b"svc%d" % i).ljust(32, b"\0")) for i in range(signers)]
    out = []
    for i in range(n):
        s = sgs[i % signers]
        msg = tag + b"payload-%d" % i
        out.append((msg, s.sign(msg), s.verkey))
    return out


@contextlib.contextmanager
def _served(tmp_path, inner):
    """A live server on `inner` + a factory for connected clients."""
    from plenum_tpu.parallel.crypto_service import (CryptoPlaneServer,
                                                    ServiceEd25519Verifier)
    sock = str(tmp_path / "crypto.sock")
    server = CryptoPlaneServer(inner, socket_path=sock)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    async def run():
        await server.start()
        started.set()
        while not server._stop.is_set():
            await asyncio.sleep(0.05)
        await server.stop()

    t = threading.Thread(target=lambda: loop.run_until_complete(run()),
                         daemon=True)
    t.start()
    assert started.wait(5.0)
    clients = []

    def connect():
        c = ServiceEd25519Verifier(socket_path=sock)
        clients.append(c)
        return c

    try:
        yield server, connect
    finally:
        for c in clients:
            c.close()
        server._stop.set()
        t.join(timeout=5.0)


@pytest.fixture
def service(tmp_path):
    """A live server on a CPU verifier + a factory for connected clients."""
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    with _served(tmp_path, CpuEd25519Verifier()) as served:
        yield served


def test_verdicts_match_direct_verification(service):
    server, connect = service
    ver = connect()
    items = _make_items(12)
    # corrupt two: flipped sig byte, wrong key
    items[3] = (items[3][0], items[3][1][:32] + bytes(32), items[3][2])
    items[7] = (items[7][0], items[7][1], items[0][2])
    out = ver.verify_batch(items)
    expected = np.ones(12, dtype=bool)
    expected[3] = expected[7] = False
    assert (out == expected).all()


def test_cache_dedupes_across_clients(service):
    server, connect = service
    a, b = connect(), connect()
    items = _make_items(20, tag=b"dedup")
    assert a.verify_batch(items).all()
    dispatched_before = server.stats["dispatched_items"]
    assert b.verify_batch(items).all()          # same content, other client
    # nothing new dispatched: B rode A's cached verdicts
    assert server.stats["dispatched_items"] == dispatched_before
    assert server.stats["cache_hits"] >= 20


def test_pipelined_submit_collect(service):
    _, connect = service
    ver = connect()
    t1 = ver.submit_batch(_make_items(5, tag=b"one"))
    t2 = ver.submit_batch(_make_items(5, tag=b"two"))
    # out-of-order collection: replies are matched by id
    assert ver.collect_batch(t2, wait=True).all()
    assert ver.collect_batch(t1, wait=True).all()


def test_malformed_items_are_false_not_fatal(service):
    _, connect = service
    ver = connect()
    good = _make_items(2)
    bad = [(b"msg", b"short-sig", b"short-key"), good[0], (b"", b"", b"")]
    out = ver.verify_batch(bad)
    assert list(out) == [False, True, False]


def test_connect_fails_fast_without_server(tmp_path):
    from plenum_tpu.parallel.crypto_service import ServiceEd25519Verifier
    with pytest.raises(OSError):
        ServiceEd25519Verifier(socket_path=str(tmp_path / "nope.sock"))


def test_tcp_pool_over_crypto_service():
    """The topology this exists for: a 4-process pool whose nodes all
    verify through ONE crypto-plane process (backend service:cpu), with
    the verdict cache collapsing per-node re-verification."""
    pytest.importorskip(
        "cryptography",
        reason="the TCP node stack's handshake needs the cryptography package")
    from plenum_tpu.tools.tcp_pool import run_tcp_pool
    r = run_tcp_pool(n_nodes=4, n_txns=60, backend="service:cpu",
                     timeout=90.0)
    assert r["txns_ordered"] == 60, r
    stats = r.get("crypto_service")
    assert stats, "service stats missing from the bench result"
    # 4 nodes x 60 requests: without the cache the plane would dispatch
    # ~4x the unique signatures; with it, roughly one dispatch per unique
    # signature (trustee + 60 users, plus handshake traffic)
    assert stats["cache_hits"] > 0
    assert stats["dispatched_items"] < stats["items"]


def test_cache_poisoning_by_field_shift_rejected(service):
    """(msg, sig+vk[:1], vk[1:]) must NOT share a cache digest with the
    honest (msg, sig, vk): every field is length-prefixed. An attacker
    pre-submitting the shifted triple (malformed -> False) must not make
    the plane reject the honest signature afterwards."""
    _, connect = service
    attacker, honest = connect(), connect()
    (msg, sig, vk) = _make_items(1, tag=b"poison")[0]
    shifted = (msg, sig + vk[:1], vk[1:])
    assert not attacker.verify_batch([shifted]).any()   # cached False
    assert honest.verify_batch([(msg, sig, vk)]).all()  # unaffected


def test_backend_failure_is_loud_and_worker_survives(tmp_path):
    """An inner-verifier exception (the device dying) must surface
    as an error to waiting clients — never a silent all-False verdict or
    a dead worker thread that wedges every node."""
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    from plenum_tpu.parallel.crypto_service import (CryptoPlaneServer,
                                                    ServiceEd25519Verifier)

    class FlakyVerifier(CpuEd25519Verifier):
        def __init__(self):
            super().__init__()
            self.fail_next = True

        def verify_batch(self, items):
            if self.fail_next:
                self.fail_next = False
                raise RuntimeError("device dropped")
            return super().verify_batch(items)

    sock = str(tmp_path / "crypto.sock")
    server = CryptoPlaneServer(FlakyVerifier(), socket_path=sock)
    loop_ready = threading.Event()

    def runner():
        async def run():
            await server.start()
            loop_ready.set()
            while not server._stop.is_set():
                await asyncio.sleep(0.05)
        asyncio.new_event_loop().run_until_complete(run())

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    assert loop_ready.wait(5.0)
    ver = ServiceEd25519Verifier(socket_path=sock)
    items = _make_items(3, tag=b"flaky")
    with pytest.raises(RuntimeError, match="device dropped"):
        ver.verify_batch(items)
    # the worker survived: the next dispatch succeeds
    assert ver.verify_batch(items).all()
    # the swallow was NAMED, not silent: the audit counters recorded it
    assert server.stats.get("submit_errors", 0) + \
        server.stats.get("collect_errors", 0) >= 1
    ver.close()
    server._stop.set()
    t.join(timeout=5.0)


def test_supervised_inner_degrades_server_to_cpu_not_errors(tmp_path):
    """The production server topology: its inner device verifier rides
    the plane supervisor, so a wedged device yields CPU-hedged VERDICTS
    to every client — not error replies — and the stats op exposes the
    breaker state over the socket."""
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    from plenum_tpu.parallel.crypto_service import (CryptoPlaneServer,
                                                    ServiceEd25519Verifier)
    from plenum_tpu.parallel.faults import FaultyVerifier
    from plenum_tpu.parallel.supervisor import (CircuitBreaker,
                                                DeadlineBudget,
                                                SupervisedVerifier)
    device = FaultyVerifier(CpuEd25519Verifier())
    inner = SupervisedVerifier(
        device, fallback=CpuEd25519Verifier(),
        breaker=CircuitBreaker(fail_threshold=2, cooldown=30.0),
        budget=DeadlineBudget(base=0.2, min_s=0.15, warm_max=0.3,
                              cold_max=0.3))
    sock = str(tmp_path / "crypto.sock")
    server = CryptoPlaneServer(inner, socket_path=sock)
    started = threading.Event()

    def runner():
        async def run():
            await server.start()
            started.set()
            while not server._stop.is_set():
                await asyncio.sleep(0.02)
        asyncio.new_event_loop().run_until_complete(run())

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    assert started.wait(5.0)
    try:
        ver = ServiceEd25519Verifier(socket_path=sock)
        good = _make_items(3, tag=b"sup-ok")
        assert ver.verify_batch(good).all()
        device.wedge()
        mixed = _make_items(4, tag=b"sup-wedge")
        mixed[1] = (mixed[1][0], mixed[1][1][:32] + bytes(32), mixed[1][2])
        # verdicts, not errors: the server hedged on its CPU fallback
        out = ver.verify_batch(mixed)
        assert list(out) == [True, False, True, True]
        stats = ver.stats()
        assert stats["plane"]["hedge_wins"] >= 1
        assert stats["plane"]["verdict_forks"] == 0
        ver.close()
    finally:
        server._stop.set()
        t.join(timeout=5.0)


def test_bls_checks_ride_the_plane_and_dedupe(service):
    """The per-batch BLS aggregate check is the other identical-on-every-
    node pairing; routed through the plane it runs once per host."""
    from plenum_tpu.crypto import bls as bls_mod
    from plenum_tpu.crypto.bls import BlsCryptoSigner, aggregate_sigs
    from plenum_tpu.parallel.crypto_service import ServiceBlsVerifier

    server, connect = service
    signers = [BlsCryptoSigner(seed=b"svcbls%d" % i + bytes(25))
               for i in range(3)]
    message = b"state-root-over-the-plane"
    agg = aggregate_sigs([s.sign(message) for s in signers])
    vks = [s.pk for s in signers]

    a = ServiceBlsVerifier(socket_path=connect().socket_path)
    bls_mod._BLS_VERDICTS.clear()
    assert a.verify_multi_sig(agg, message, vks)
    pairings_after_first = server.stats.get("bls_pairings", 0)
    assert pairings_after_first >= 1

    # the REAL cross-process claim: a separate OS process (fresh local
    # cache) asking the same check costs the server a lookup, not a
    # pairing — and different verkey order must not change the verdict
    import base64
    import pickle
    import subprocess
    import sys
    blob = base64.b64encode(pickle.dumps(
        (a._client.socket_path, agg, message, list(reversed(vks))))).decode()
    code = (
        "import base64, pickle, sys\n"
        "sock, agg, msg, vks = pickle.loads(base64.b64decode('" + blob + "'))\n"
        "from plenum_tpu.parallel.crypto_service import ServiceBlsVerifier\n"
        "v = ServiceBlsVerifier(socket_path=sock)\n"
        "assert v.verify_multi_sig(agg, msg, vks)\n"
        "print('XPROC-OK')\n")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "XPROC-OK" in out.stdout, out.stderr[-500:]
    assert server.stats.get("bls_pairings", 0) == pairings_after_first

    # wrong participant set still fails closed
    assert not a.verify_multi_sig(agg, message, vks[:2])
    assert not a.verify_multi_sig(agg, b"other", vks)
    a.close()


def test_bls_single_flight_survives_cancellation(tmp_path):
    """A client disconnect cancels its _process task mid-pairing; the
    single-flight future must still resolve (and the key must be popped)
    so later identical checks don't await a dead future forever."""
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    from plenum_tpu.parallel.crypto_service import CryptoPlaneServer
    server = CryptoPlaneServer(CpuEd25519Verifier(),
                               socket_path=str(tmp_path / "c.sock"))
    in_pairing = threading.Event()
    release = threading.Event()

    def slow_verify(sig, msg, vks):
        in_pairing.set()
        assert release.wait(5.0)
        return True

    server._bls.verify_multi_sig = slow_verify
    msg = b"cancel-regression-%d" % os.getpid()   # dodge the global cache

    async def scenario():
        loop = asyncio.get_running_loop()
        first = asyncio.ensure_future(
            server._bls_check(loop, "sig", msg, ["vk1", "vk2"]))
        while not in_pairing.is_set():
            await asyncio.sleep(0.01)
        first.cancel()                 # the disconnecting client
        with pytest.raises(asyncio.CancelledError):
            await first
        # identical check from a co-hosted node: joins the in-flight
        # pairing and must resolve once it completes
        second = asyncio.ensure_future(
            server._bls_check(loop, "sig", msg, ["vk1", "vk2"]))
        await asyncio.sleep(0.05)
        release.set()
        return await asyncio.wait_for(second, timeout=5.0)

    assert asyncio.run(scenario()) is True
    assert server._bls_pending == {}


# --- double-buffered worker (round 5) -------------------------------------

class _SlowAsyncVerifier:
    """Inner verifier with REAL async token semantics: submit returns
    immediately, the 'device' resolves each token ~30 ms later in a
    background thread — enough for the worker to stage the next wave."""

    def __init__(self):
        from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
        self._cpu = CpuEd25519Verifier()
        self.submitted = []

    def submit_batch(self, items):
        import time
        self.submitted.append([i[0] for i in items])
        return {"t": time.monotonic() + 0.03,
                "verdicts": self._cpu.verify_batch(items)}

    def collect_batch(self, token, wait=True):
        import time
        while time.monotonic() < token["t"]:
            if not wait:
                return None
            time.sleep(0.002)
        return token["verdicts"]

    def verify_batch(self, items):
        return self.collect_batch(self.submit_batch(items), wait=True)


def test_worker_overlaps_waves_and_dedupes_across_them(tmp_path):
    """Wave k+1 must dispatch while wave k is still in flight (overlap),
    and content already computing in wave k must NOT be re-dispatched by a
    later wave — the job rides the in-flight wave."""
    from plenum_tpu.parallel.crypto_service import (CryptoPlaneServer,
                                                    ServiceEd25519Verifier)
    sock = str(tmp_path / "crypto.sock")
    inner = _SlowAsyncVerifier()
    server = CryptoPlaneServer(inner, socket_path=sock)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    async def run():
        await server.start()
        started.set()
        while not server._stop.is_set():
            await asyncio.sleep(0.02)
        await server.stop()

    t = threading.Thread(target=lambda: loop.run_until_complete(run()),
                         daemon=True)
    t.start()
    assert started.wait(5.0)
    try:
        c1 = ServiceEd25519Verifier(socket_path=sock)
        c2 = ServiceEd25519Verifier(socket_path=sock)
        a = _make_items(6, tag=b"waveA-")
        b = _make_items(6, tag=b"waveB-")
        # wave 1: client 1 ships A; then while it is in flight, client 2
        # ships B (new content -> second wave overlapped) AND A again
        # (must attach to wave 1, not re-dispatch)
        t1 = c1.submit_batch(a)
        import time
        time.sleep(0.005)                  # let the worker pick up wave 1
        t2 = c2.submit_batch(b)
        t3 = c2.submit_batch(a)
        ok1 = c1.collect_batch(t1)
        ok2 = c2.collect_batch(t2)
        ok3 = c2.collect_batch(t3)
        assert ok1.all() and ok2.all() and ok3.all()
        # A's messages were dispatched exactly once across all waves
        flat = [m for batch in inner.submitted for m in batch]
        assert len(flat) == len(set(flat)), "re-dispatched content"
        assert server.stats.get("overlapped", 0) >= 1, server.stats
        c1.close(); c2.close()
    finally:
        server._stop.set()
        t.join(timeout=5.0)


def test_submit_failure_with_cross_wave_dependency_is_loud(tmp_path):
    """Regression (round-5 review): wave 1 in flight, a job referencing
    wave-1 content plus new content attaches to wave 2; wave 2's submit
    raises. The job must get an ERROR reply (not hang) and the worker
    thread must survive to serve later requests."""
    from plenum_tpu.parallel.crypto_service import (CryptoPlaneServer,
                                                    ServiceEd25519Verifier)

    class _FlakySubmit(_SlowAsyncVerifier):
        def __init__(self):
            super().__init__()
            self.fail_next = False

        def submit_batch(self, items):
            if self.fail_next:
                self.fail_next = False
                raise RuntimeError("device dropped")
            return super().submit_batch(items)

    sock = str(tmp_path / "crypto.sock")
    inner = _FlakySubmit()
    server = CryptoPlaneServer(inner, socket_path=sock)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    async def run():
        await server.start()
        started.set()
        while not server._stop.is_set():
            await asyncio.sleep(0.02)
        await server.stop()

    t = threading.Thread(target=lambda: loop.run_until_complete(run()),
                         daemon=True)
    t.start()
    assert started.wait(5.0)
    try:
        c = ServiceEd25519Verifier(socket_path=sock)
        a = _make_items(4, tag=b"dep-a-")
        b = _make_items(4, tag=b"dep-b-")
        t1 = c.submit_batch(a)
        import time
        time.sleep(0.005)            # wave 1 (a) now in flight
        inner.fail_next = True
        t2 = c.submit_batch(a + b)   # depends on wave 1 AND the failing wave
        assert c.collect_batch(t1).all()
        with pytest.raises(RuntimeError):
            c.collect_batch(t2)      # loud error, not a hang
        # worker alive: a fresh request still round-trips
        t3 = c.submit_batch(_make_items(3, tag=b"dep-c-"))
        assert c.collect_batch(t3).all()
        assert server._worker.is_alive()
        c.close()
    finally:
        server._stop.set()
        t.join(timeout=5.0)


# --- federated wave frames + prewarm/pin negotiation -------------------------

def test_federated_wave_frames_dispatch_verbatim(service):
    """`"wave": 1` frames bypass the server's dedup/coalescing: a padded
    bucket of IDENTICAL items dispatches at full width (the federated
    lane's pinned-shape guarantee crosses the wire), while verdicts stay
    correct and still land in the shared digest cache."""
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    server, connect = service
    fed = FederatedEd25519Client(socket_path=connect().socket_path)
    pad = _make_items(1, tag=b"pad")[0]
    before = server.stats["dispatched_items"]
    out = fed.collect_batch(fed.submit_batch([pad] * 16), wait=True)
    assert out.shape == (16,) and out.all()
    assert server.stats["dispatched_items"] - before == 16, \
        "server deduped a wave frame — the dispatched shape shrank"
    assert server.stats.get("wave_frames", 0) >= 1
    # mixed real verdicts round-trip the raw path too
    items = _make_items(6, tag=b"wavemix")
    items[2] = (items[2][0], items[2][1][:32] + bytes(32), items[2][2])
    got = fed.collect_batch(fed.submit_batch(items), wait=True)
    assert list(got) == [True, True, False, True, True, True]
    fed.close()


def test_federated_prewarm_pin_negotiation(service):
    """The prewarm RPC compiles each pad bucket server-side (one
    verbatim all-pad wave per bucket) and answers whether the remote
    inner is device-backed; pin marks warmup over."""
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    server, connect = service
    fed = FederatedEd25519Client(socket_path=connect().socket_path)
    reply = fed.prewarm([8, 16])
    assert reply["warmed"] == [8, 16]
    assert reply["bucketed"] is False       # CPU inner: don't pad for it
    assert server.stats.get("prewarms") == 1
    assert fed.pin()["pinned"] is True
    assert server.stats.get("pinned") == 1
    fed.close()


def test_prewarm_full_key_tables_and_loud_failure(service):
    """full_keys warms a second, all-distinct-verkey wave per bucket (the
    full key-table shape a plain client's coalesced waves dispatch); a
    warm wave the supervised inner answered from the CPU is an ERROR
    reply, never a bucket reported compiled; stats() names the owner's
    device (None for a host inner) and its compile counters."""
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    from plenum_tpu.parallel.faults import FaultyVerifier
    from plenum_tpu.parallel.supervisor import supervise
    server, connect = service
    faulty = FaultyVerifier(CpuEd25519Verifier())
    server._inner = supervise(faulty)
    fed = FederatedEd25519Client(socket_path=connect().socket_path)
    frames = server.stats.get("wave_frames", 0)
    assert fed.prewarm([8, 16], full_keys=True)["warmed"] == [8, 16]
    assert server.stats["wave_frames"] - frames == 4
    st = fed.stats()
    assert st["device"] is None and st["compile"]["executables"] >= 0
    assert st["plane"]["device_batches"] == 4

    faulty.drop()                       # the device now refuses dispatches
    with pytest.raises(RuntimeError, match="not answered by the device"):
        fed.prewarm([8])
    fed.close()


def test_prewarm_rpc_preloads_both_key_tables_before_its_waves(service):
    """The RPC knows both key-table shapes from full_keys and hands the
    inner's preload() all of them before the first wave (a device inner
    obtains them at once, through the executable store)."""
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    calls = []

    class Recording(CpuEd25519Verifier):
        def preload(self, waves):
            calls.append(("preload", sorted(waves)))
            return []

        def submit_batch(self, items):
            calls.append(("wave", len(items),
                          len({vk for _, _, vk in items})))
            return super().submit_batch(items)

    server, connect = service
    server._inner = Recording()
    fed = FederatedEd25519Client(socket_path=connect().socket_path)
    assert fed.prewarm([16], full_keys=True)["warmed"] == [16]
    fed.close()
    assert calls == [("preload", [(16, 1), (16, 16)]), ("wave", 16, 1),
                     ("wave", 16, 16)]


def _recording(calls, base, **kwargs):
    """`base` with its preload and its waves written down; a device
    (jax) double answers every lane False without a device."""
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier

    class Recording(base):
        def preload(self, waves):
            calls.append(("preload", sorted(waves)))
            return []

        def submit_batch(self, items):
            calls.append(("wave", len(items),
                          len({vk for _, _, vk in items})))
            if issubclass(base, JaxEd25519Verifier):
                return np.zeros(len(items), dtype=bool)
            return super().submit_batch(items)

    return Recording(**kwargs)


@pytest.mark.parametrize("inner, small", [
    ("jax-512", True), ("jax-128-supervised", True), ("jax-64", False),
    ("jax-16", False), ("host", False)])
def test_prewarm_holds_the_small_program_beside_a_large_min_batch(
        service, inner, small):
    """A device-backed inner whose min_batch is above the small bucket
    gets the 64-lane program in the SAME preload as the buckets asked
    for (one call, on the loop's thread: a load from any other costs
    50-75 s), proven by an all-pad wave like the others, before pin is
    answered. A host inner, or a min_batch of 64 or less, gets nothing
    added. `warmed` names only what was asked for."""
    from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier,
                                           JaxEd25519Verifier)
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    from plenum_tpu.parallel.supervisor import supervise
    calls = []
    server, connect = service
    if inner == "host":
        server._inner = _recording(calls, CpuEd25519Verifier)
    else:
        device = _recording(calls, JaxEd25519Verifier,
                            min_batch=int(inner.split("-")[1]))
        server._inner = supervise(device) if "supervised" in inner \
            else device
    fed = FederatedEd25519Client(socket_path=connect().socket_path)
    reply = fed.prewarm([512], full_keys=True)
    assert reply["warmed"] == [512]
    assert reply["bucketed"] is (inner != "host")
    at_prewarm = list(calls)
    assert fed.pin()["pinned"] is True
    fed.close()
    assert calls == at_prewarm              # nothing is obtained after pin
    want_waves = [(512, 1), (512, 512)] + ([(64, 1)] if small else [])
    assert calls == [("preload", sorted(want_waves))] \
        + [("wave", *w) for w in want_waves]


def test_prewarm_asked_for_the_small_bucket_warms_it_once(service):
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    calls = []
    server, connect = service
    server._inner = _recording(calls, JaxEd25519Verifier, min_batch=512)
    fed = FederatedEd25519Client(socket_path=connect().socket_path)
    assert fed.prewarm([64, 512])["warmed"] == [64, 512]
    fed.close()
    assert calls == [("preload", [(64, 1), (512, 1)]), ("wave", 64, 1),
                     ("wave", 512, 1)]


def _forged(items, at):
    msg, sig, vk = items[at]
    items[at] = (msg, bytes([sig[0] ^ 1]) + sig[1:], vk)    # a bit in R
    return items


@pytest.mark.parametrize("n, signers, lanes", [
    (9, 4, "64"), (64, 64, "64"), (65, 8, "512"), (100, 100, "512")])
def test_a_wave_runs_in_the_smallest_held_program_and_is_counted_there(
        service, monkeypatch, n, signers, lanes):
    """The served plane as the benchmark starts it (min_batch 512,
    prewarm [512] x both key tables, pin), the store stubbed with
    programs that answer from a table of CpuEd25519Verifier's verdicts:
    a wave of nine lands in the 64-lane program and a wave of a hundred
    in the 512-lane one, `dispatches_by_lanes` says so, and the verdicts
    are the CPU's, the forged item's included."""
    import jax.numpy as jnp
    from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier,
                                           JaxEd25519Verifier)
    from plenum_tpu.ops import aot
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    from plenum_tpu.parallel.supervisor import supervise
    items = _forged(_make_items(n, signers=signers, tag=b"lanes%d" % n),
                    n // 2)
    want = CpuEd25519Verifier().verify_batch(items)
    assert list(want) == [i != n // 2 for i in range(n)]
    truth = {sig: bool(ok) for (_, sig, _), ok in zip(items, want)}
    ran = []

    def program(jitted, avals, device=None, wait=True):
        shape = (avals[0].shape[0], avals[2].shape[0])

        def run(s, h, keys, idx, r):
            assert (s.shape[0], keys.shape[0]) == shape
            ran.append(shape)
            s, r = np.asarray(s), np.asarray(r)
            return jnp.asarray([truth.get(bytes(r[j]) + bytes(s[j]), False)
                                for j in range(shape[0])])
        return run
    monkeypatch.setattr(aot, "has_entry", lambda *a, **k: True)
    monkeypatch.setattr(aot, "obtain", program)
    server, connect = service
    server._inner = supervise(JaxEd25519Verifier(min_batch=512))
    client = connect()
    fed = FederatedEd25519Client(socket_path=client.socket_path)
    assert fed.prewarm([512], full_keys=True)["warmed"] == [512]
    fed.pin()
    fed.close()
    assert client.stats()["dispatches_by_lanes"] == {"64": 1, "512": 2}
    assert ran == [(512, 64), (512, 512), (64, 64)]
    got = client.verify_batch(items)
    assert (got == want).all()
    st = client.stats()
    by_lanes = {"64": 1, "512": 2}
    by_lanes[lanes] += 1
    assert st["dispatches_by_lanes"] == by_lanes
    assert st["dispatches"] == sum(by_lanes.values())
    assert ran[-1] == (int(lanes), 64 if signers <= 64 else int(lanes))
    assert not any(st["plane"][k] for k in ("fallback_batches",
                                            "device_errors"))


def test_a_host_inner_counts_no_lanes(service):
    server, connect = service
    client = connect()
    assert client.verify_batch(_make_items(9, tag=b"host-lanes")).all()
    st = client.stats()
    assert st["dispatches"] == 1 and st["dispatches_by_lanes"] == {}


def test_federated_pipeline_rides_remote_lane(service):
    """End-to-end: a FederatedCryptoPipeline with one REAL remote lane
    over the service socket — prewarm negotiation turns padding off for
    the CPU-backed host, unhinted waves land on the remote, verdicts
    are correct, and no item is double-verified."""
    from plenum_tpu.config import Config
    from plenum_tpu.crypto.ed25519 import JaxEd25519Verifier
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    from plenum_tpu.parallel.federation import FederatedCryptoPipeline
    from plenum_tpu.parallel.supervisor import supervise
    server, connect = service
    sock = connect().socket_path
    class FakeDev(JaxEd25519Verifier):
        def __init__(self):
            super().__init__(min_batch=1)

        def submit_batch(self, items):
            return np.ones(len(items), dtype=bool)

        def collect_batch(self, token, wait=True):
            return token

    fed = supervise(FederatedEd25519Client(socket_path=sock),
                    label="remote0")
    pipe = FederatedCryptoPipeline(
        ed_inners=[FakeDev()],
        remote_inners=[fed], hosts=[sock],
        config=Config(PIPELINE_MIN_BUCKET=16, PIPELINE_MAX_BUCKET=64,
                      PIPELINE_FLUSH_WAIT=0.0),
        threaded=False)
    pipe.prewarm([16])
    assert pipe.lanes[1].bucketed is False  # negotiated: CPU host
    pipe.pin()
    n = 0
    toks = []
    for i in range(8):
        items = _make_items(4, tag=b"fed%d-" % i)
        toks.append(pipe.submit_verify(items))
        n += 4
    for t in toks:
        out = pipe.collect_verify(t, wait=True)
        assert out is not None and out.all()
    assert pipe.lanes[1].stats["dispatches"] >= 1, \
        "the remote lane never carried a wave"
    assert pipe.stats["dispatched_items"] == n
    assert pipe.stats["unpinned_shapes"] == 0
    assert pipe.federation_state()["remote_lanes"] == 1
    assert pipe.federation_state()["ship_ms_p95"] > 0.0
    pipe.close()


# --- the service's own two waits (`stats()["waits"]`) -----------------------

# what `stats` answered before the waits: the benchmark's snapshot() reads
# eight of them by name
OLD_STATS_KEYS = ("batches", "items", "cache_hits", "dispatches",
                  "dispatched_items", "cache_size", "dispatches_by_lanes",
                  "device", "compile")


def test_service_waits_count_jobs_and_pin_resets_them(service):
    """One `queue` and one `wave` sample a job (one client's batch as it
    came off the socket); `pin` starts both afresh so warm-up stays
    outside; every old stats key is still there."""
    from plenum_tpu.parallel.crypto_service import FederatedEd25519Client
    server, connect = service
    client = connect()
    for i in range(5):
        assert client.verify_batch(
            _make_items(6, tag=b"waits-%d" % i)).all()
    st = client.stats()
    assert all(k in st for k in OLD_STATS_KEYS), sorted(st)
    waits = st["waits"]
    assert set(waits) == {"queue", "wave"}
    for w in waits.values():
        assert set(w) == {"count", "sum_s", "p50_ms", "p95_ms"}
        assert w["count"] == st["batches"] == 5
        assert w["sum_s"] >= 0 and w["p95_ms"] >= w["p50_ms"] >= 0
    assert waits["wave"]["sum_s"] > 0           # each job rode a wave

    fed = FederatedEd25519Client(socket_path=client.socket_path)
    assert fed.pin()["pinned"] is True
    fed.close()
    assert all(w == {"count": 0, "sum_s": 0.0, "p50_ms": None,
                     "p95_ms": None}
               for w in client.stats()["waits"].values())
    assert client.verify_batch(_make_items(6, tag=b"waits-after")).all()
    after = client.stats()
    assert after["waits"]["queue"]["count"] == 1
    assert after["batches"] == 6                # the old counter runs on


def test_a_pure_cache_hit_waits_for_no_wave(service):
    server, connect = service
    a, b = connect(), connect()
    items = _make_items(10, tag=b"hit-waits")
    assert a.verify_batch(items).all()
    first = a.stats()["waits"]["wave"]
    assert first["count"] == 1 and first["sum_s"] > 0
    assert b.verify_batch(items).all()          # every verdict cached
    second = b.stats()["waits"]
    assert second["wave"]["count"] == 2
    assert second["wave"]["sum_s"] == first["sum_s"]    # the hit added 0
    assert second["queue"]["count"] == 2


def test_a_job_riding_a_wave_in_flight_waits_until_it_lands(tmp_path):
    """Two clients, the same content, the second while the first's wave
    is out: ONE dispatch, and the second job's wave wait runs until that
    wave lands (not 0, not a second dispatch)."""
    import time
    inner = _SlowAsyncVerifier()
    with _served(tmp_path, inner) as (server, connect):
        a, b = connect(), connect()
        items = _make_items(8, tag=b"ride")
        tok_a = a.submit_batch(items)
        time.sleep(0.01)                # a's wave is out (30 ms)
        tok_b = b.submit_batch(items)
        assert b.collect_batch(tok_b, wait=True).all()
        assert a.collect_batch(tok_a, wait=True).all()
        st = a.stats()
        assert st["dispatches"] == 1 and len(inner.submitted) == 1
        wave = st["waits"]["wave"]
        assert wave["count"] == 2
        # a waited the wave's 30 ms, b what was left of it: both > 0
        assert wave["sum_s"] > 0.03 and wave["p50_ms"] > 1.0
