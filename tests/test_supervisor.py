"""Self-healing crypto plane (parallel/supervisor.py + parallel/faults.py):
breaker lifecycle, re-warm before re-admission, flap hysteresis, adaptive
deadlines with hedged CPU fallback (no-fork invariant), backpressure, and
the per-request deadline budget of the service client — driven by the
deterministic fault injector on an injected clock, plus real-wall-clock
integration against a live CryptoPlaneServer."""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier, Ed25519Signer,
                                       make_verifier)
from plenum_tpu.parallel.faults import FaultPlan, FaultyVerifier
from plenum_tpu.parallel.supervisor import (CLOSED, HALF_OPEN, OPEN,
                                            CircuitBreaker, DeadlineBudget,
                                            SupervisedVerifier,
                                            find_supervisor, supervise)

_signer = Ed25519Signer(seed=b"supervisor-tests".ljust(32, b"\0"))


def _items(tag: bytes, n: int = 3, bad: int = -1):
    out = []
    for i in range(n):
        msg = tag + b"-%d" % i
        sig = _signer.sign(msg if i != bad else msg + b"!")
        out.append((msg, sig, _signer.verkey))
    return out


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _plane(fail_threshold=2, cooldown=1.0, **budget_kw):
    clock = _Clock()
    dev = FaultyVerifier(CpuEd25519Verifier(), now=clock)
    sup = SupervisedVerifier(
        dev, fallback=CpuEd25519Verifier(),
        breaker=CircuitBreaker(fail_threshold=fail_threshold,
                               cooldown=cooldown, now=clock),
        budget=DeadlineBudget(base=0.3, min_s=0.2, warm_max=1.0,
                              cold_max=1.0, **budget_kw),
        now=clock)
    return clock, dev, sup


# --- breaker lifecycle ------------------------------------------------------


def test_closed_to_open_on_k_consecutive_deadline_misses():
    clock, dev, sup = _plane(fail_threshold=2)
    assert sup.verify_batch(_items(b"warm")).all()
    assert sup.breaker.state == CLOSED
    dev.wedge()
    for i in range(2):
        tok = sup.submit_batch(_items(b"wedged-%d" % i))
        assert tok.kind == "dev"
        clock.advance(2.0)                       # past the deadline budget
        verdicts = sup.collect_batch(tok, wait=False)
        assert verdicts is not None and verdicts.all()   # hedged, correct
    assert sup.breaker.state == OPEN
    assert sup.breaker.opens == 1
    assert sup.stats["deadline_misses"] == 2
    # open circuit: dispatch routes to CPU INSTANTLY (no device submit)
    before = dev.submits
    tok = sup.submit_batch(_items(b"instant"))
    assert tok.kind == "cpu" and dev.submits == before
    assert sup.collect_batch(tok).all()
    assert sup.stats["open_circuit_fallbacks"] >= 1


def test_device_errors_also_trip_the_breaker():
    clock, dev, sup = _plane(fail_threshold=3)
    dev.drop()                                  # connection refused
    for i in range(3):
        assert sup.verify_batch(_items(b"drop-%d" % i)).all()
    assert sup.breaker.state == OPEN
    assert sup.stats["device_errors"] == 3


def test_half_open_probe_rewarns_before_readmitting():
    clock, dev, sup = _plane(fail_threshold=1, cooldown=1.0)
    dev.corrupt()
    assert sup.verify_batch(_items(b"c")).all()          # error -> open
    assert sup.breaker.state == OPEN
    dev.heal()
    clock.advance(1.5)                                   # cooldown elapsed
    sup.submit_batch(_items(b"trigger"))                 # starts the probe
    assert sup.breaker.state in (HALF_OPEN, CLOSED)
    assert dev.rewarms == 1, "re-warm must precede the probe dispatch"
    sup.submit_batch(_items(b"poll"))                    # probe lands
    assert sup.breaker.state == CLOSED
    # the device is genuinely re-admitted
    tok = sup.submit_batch(_items(b"back"))
    assert tok.kind == "dev" and sup.collect_batch(tok).all()


def test_probe_verdict_must_be_correct_not_just_present():
    """A device that answers but answers WRONG (all-True garbage) must not
    be re-admitted: the probe carries a known-bad signature."""

    class _LyingVerifier(CpuEd25519Verifier):
        def verify_batch(self, items):
            return np.ones(len(items), dtype=bool)

    clock = _Clock()
    dev = FaultyVerifier(_LyingVerifier(), now=clock)
    sup = SupervisedVerifier(
        dev, fallback=CpuEd25519Verifier(),
        breaker=CircuitBreaker(fail_threshold=1, cooldown=1.0, now=clock),
        budget=DeadlineBudget(base=0.3, min_s=0.2, warm_max=1.0,
                              cold_max=1.0),
        now=clock)
    dev.drop()
    sup.verify_batch(_items(b"x"))
    assert sup.breaker.state == OPEN
    dev.heal()
    clock.advance(1.5)
    sup.submit_batch(_items(b"t1"))          # probe starts
    sup.submit_batch(_items(b"t2"))          # probe lands: [True, True] != expected
    assert sup.breaker.state == OPEN, "lying device must stay quarantined"
    assert sup.stats["probe_failures"] >= 1


def test_flap_hysteresis_doubles_cooldown_and_decays():
    clock, dev, sup = _plane(fail_threshold=1, cooldown=1.0)
    base = sup.breaker.cooldown

    def flap_once():
        dev.wedge()
        tok = sup.submit_batch(_items(b"f%f" % clock.t))
        clock.advance(2.0)
        sup.collect_batch(tok, wait=False)            # miss -> open
        assert sup.breaker.state == OPEN
        dev.heal()
        clock.advance(sup.breaker.cooldown + 0.1)
        sup.submit_batch(_items(b"p%f" % clock.t))    # probe starts
        sup.submit_batch(_items(b"q%f" % clock.t))    # probe lands -> close
        assert sup.breaker.state == CLOSED

    flap_once()
    after_one = sup.breaker.cooldown          # first open: base cooldown
    flap_once()
    after_two = sup.breaker.cooldown
    flap_once()
    after_three = sup.breaker.cooldown
    # every RE-open (an open before the decay window passed) doubles the
    # probe cooldown: a flapping device faces exponentially rarer probes,
    # not a thrash loop
    assert after_one == base
    assert after_two == base * 2
    assert after_three == base * 4
    # hysteresis decay: a long run of healthy traffic restores the base
    for i in range(sup.breaker.reset_after + 1):
        assert sup.verify_batch(_items(b"ok-%d" % i, n=1)).all()
    assert sup.breaker.cooldown == base


def test_failed_probe_reopens_with_longer_cooldown():
    clock, dev, sup = _plane(fail_threshold=1, cooldown=1.0)
    dev.wedge()
    tok = sup.submit_batch(_items(b"w"))
    clock.advance(2.0)
    sup.collect_batch(tok, wait=False)
    assert sup.breaker.state == OPEN
    clock.advance(1.5)                       # still wedged: probe will hang
    sup.submit_batch(_items(b"t"))           # probe starts (lost in wedge)
    assert sup.breaker.state == HALF_OPEN
    clock.advance(2.0)                       # probe deadline passes
    sup.submit_batch(_items(b"u"))           # reopen, cooldown doubled
    assert sup.breaker.state == OPEN
    assert sup.breaker.cooldown == 2.0
    assert sup.stats["probe_failures"] == 1


# --- hedged dispatch + no-fork invariant ------------------------------------


def test_hedged_race_verdicts_identical_per_item():
    """Device delayed past its budget: the CPU hedge answers; when the
    device verdict finally lands it is reaped and compared — identical
    per item (including the known-bad one), zero forks."""
    clock, dev, sup = _plane(fail_threshold=5)
    items = _items(b"hedge", n=5, bad=2)
    expected = [True, True, False, True, True]
    dev.delay(3.0)                            # longer than any budget
    tok = sup.submit_batch(items)
    assert sup.collect_batch(tok, wait=False) is None   # still in flight
    clock.advance(1.5)                        # past deadline
    verdicts = sup.collect_batch(tok, wait=False)
    assert list(verdicts) == expected         # CPU hedge verdict, correct
    assert sup.stats["hedge_wins"] == 1
    # the late device verdict lands; the reaper must compare and agree
    clock.advance(5.0)
    dev.heal()
    sup.submit_batch(_items(b"reap"))         # drives the zombie reaper
    assert sup.stats["late_landings"] == 1
    assert sup.stats["verdict_forks"] == 0


def test_blocking_collect_hedges_at_deadline_real_clock():
    """Wall-clock: a blocking collect on a wedged device returns the CPU
    verdict within the deadline budget — measured, not slept-and-hoped."""
    dev = FaultyVerifier(CpuEd25519Verifier())
    sup = SupervisedVerifier(
        dev, fallback=CpuEd25519Verifier(),
        budget=DeadlineBudget(base=0.4, min_s=0.3, warm_max=0.5,
                              cold_max=0.5))
    items = _items(b"block", n=4, bad=1)
    dev.wedge()
    t0 = time.monotonic()
    verdicts = sup.verify_batch(items)
    elapsed = time.monotonic() - t0
    assert list(verdicts) == [True, False, True, True]
    assert elapsed < 2.0, f"stall {elapsed:.2f}s exceeded the budget"
    assert sup.stats["hedge_wins"] == 1
    assert sup.stats["max_stall_s"] <= sup.stats["max_budget_s"] + 0.5


# --- backpressure -----------------------------------------------------------


def test_backpressure_watermark_routes_to_cpu():
    clock, dev, sup = _plane()
    sup.max_outstanding_bytes = 400
    dev.delay(10.0)                           # keep dispatches in flight
    big = _items(b"x" * 100, n=3)             # ~300+ bytes over watermark
    t1 = sup.submit_batch(big)
    assert t1.kind == "dev"
    t2 = sup.submit_batch(big)
    assert t2.kind == "cpu", "past the watermark new batches go straight to CPU"
    assert sup.stats["backpressure_fallbacks"] == 1
    assert sup.collect_batch(t2).all()


# --- deadline budget --------------------------------------------------------


def test_deadline_budget_cold_then_warm_ceiling():
    b = DeadlineBudget(base=1.0, per_item_initial=0.5, margin=2.0,
                       min_s=0.5, warm_max=10.0, cold_max=300.0)
    # cold: a first dispatch may sit behind a multi-minute compile
    assert b.budget(1000) == 300.0
    b.record(1000, 2.0)                       # first success: warmed
    assert b.budget(1000) <= 10.0
    # p99 of observed per-item cost now drives the estimate
    assert b.per_item_p99() == pytest.approx(0.002)
    assert b.budget(100) == pytest.approx(1.0 + 100 * 0.002 * 2.0)


def test_deadline_budget_scales_with_batch_size():
    b = DeadlineBudget(base=0.5, margin=4.0, min_s=0.25, warm_max=30.0)
    for _ in range(10):
        b.record(100, 0.5)                    # 5 ms/item observed
    assert b.budget(10) < b.budget(1000)
    assert b.budget(1000) == pytest.approx(0.5 + 1000 * 0.005 * 4.0)


# --- fault injector determinism ---------------------------------------------


def test_fault_plan_is_pure_function_of_seed():
    for seed in (0, 1, 7, 12345):
        a, b = FaultPlan.from_seed(seed), FaultPlan.from_seed(seed)
        assert a.windows == b.windows
    assert FaultPlan.from_seed(1).windows != FaultPlan.from_seed(2).windows


def test_fault_plan_drives_modes_by_clock():
    plan = FaultPlan([(1.0, 2.0, "wedge"), (3.0, 4.0, "drop")])
    clock = _Clock()
    dev = FaultyVerifier(CpuEd25519Verifier(), plan=plan, now=clock)
    assert dev.mode() == "ok"
    clock.t = 1.5
    assert dev.mode() == "wedge"
    clock.t = 2.5
    assert dev.mode() == "ok"
    clock.t = 3.5
    with pytest.raises(ConnectionError):
        dev.submit_batch(_items(b"planned"))


def test_wedge_loses_inflight_tokens_even_after_heal():
    clock = _Clock()
    dev = FaultyVerifier(CpuEd25519Verifier(), now=clock)
    tok = dev.submit_batch(_items(b"inflight"))
    dev.wedge()
    dev.heal()
    # the reply died with the wedge; it must never resolve
    assert dev.collect_batch(tok, wait=False) is None
    with pytest.raises(ConnectionError):
        dev.collect_batch(tok, wait=True)


# --- factory + wiring -------------------------------------------------------


def test_make_verifier_wraps_device_backends():
    jax = pytest.importorskip("jax")
    del jax
    v = make_verifier("jax", min_batch=8)
    assert isinstance(v, SupervisedVerifier)
    assert type(v._device).__name__ == "JaxEd25519Verifier"
    assert find_supervisor(v) is v
    # bare escape hatch
    v2 = make_verifier("jax", min_batch=8, supervised=False)
    assert not isinstance(v2, SupervisedVerifier)
    # cpu stays bare: there is nothing to supervise
    assert not isinstance(make_verifier("cpu"), SupervisedVerifier)


def test_supervisor_delegates_device_attributes():
    _, dev, sup = _plane()
    dev.extra_attribute = 42
    assert sup.extra_attribute == 42
    with pytest.raises(AttributeError):
        sup._not_proxied


# --- service-client deadline + live-server integration ----------------------


class _WedgeableCpu(CpuEd25519Verifier):
    """Inner verifier whose verify can be held wedged from the test."""

    def __init__(self):
        super().__init__()
        self.hold = threading.Event()

    def verify_batch(self, items):
        deadline = time.monotonic() + 30.0
        while self.hold.is_set() and time.monotonic() < deadline:
            time.sleep(0.01)
        return super().verify_batch(items)


@pytest.fixture
def live_service(tmp_path):
    import asyncio

    from plenum_tpu.parallel.crypto_service import CryptoPlaneServer
    inner = _WedgeableCpu()
    sock = str(tmp_path / "crypto.sock")
    server = CryptoPlaneServer(inner, socket_path=sock)
    started = threading.Event()

    async def run():
        await server.start()
        started.set()
        while not server._stop.is_set():
            await asyncio.sleep(0.02)
        await server.stop()

    t = threading.Thread(
        target=lambda: asyncio.new_event_loop().run_until_complete(run()),
        daemon=True)
    t.start()
    assert started.wait(5.0)
    yield server, inner, sock
    server._stop.set()
    t.join(timeout=5.0)


def test_service_client_wedge_costs_one_bounded_miss(live_service):
    """The satellite fix for the flat request_timeout=300: a wedged service
    costs ONE per-request deadline budget (a few seconds warm), measured
    on the wall clock — not a 5-minute stall per batch."""
    from plenum_tpu.parallel.crypto_service import ServiceEd25519Verifier
    server, inner, sock = live_service
    client = ServiceEd25519Verifier(socket_path=sock, request_timeout=60.0,
                                    warm_timeout=5.0)
    assert client.verify_batch(_items(b"warmup")).all()   # warms the budget
    inner.hold.set()
    t0 = time.monotonic()
    with pytest.raises(ConnectionError, match="deadline budget"):
        client.verify_batch(_items(b"wedged"))
    elapsed = time.monotonic() - t0
    inner.hold.clear()
    # warm budget: base 2s + small per-item term, nowhere near 60 or 300
    assert elapsed < 10.0, f"wedge cost {elapsed:.1f}s — deadline not applied"
    assert elapsed > 0.5
    client.close()


def test_supervised_service_client_survives_wedge_and_recovers(live_service):
    """End to end on the wall clock: supervise(service client) keeps
    returning correct verdicts through a server-side wedge (hedged CPU),
    opens the breaker, then re-admits the plane after heal + probe."""
    from plenum_tpu.parallel.crypto_service import ServiceEd25519Verifier
    server, inner, sock = live_service
    sup = SupervisedVerifier(
        ServiceEd25519Verifier(socket_path=sock, request_timeout=60.0,
                               warm_timeout=5.0),
        fallback=CpuEd25519Verifier(),
        breaker=CircuitBreaker(fail_threshold=2, cooldown=0.3),
        budget=DeadlineBudget(base=0.4, min_s=0.3, warm_max=0.6,
                              cold_max=0.6))
    assert sup.verify_batch(_items(b"pre", bad=0)).tolist() == \
        [False, True, True]
    inner.hold.set()
    t0 = time.monotonic()
    for i in range(3):                        # misses open the breaker
        assert sup.verify_batch(_items(b"mid-%d" % i, bad=1)).tolist() == \
            [True, False, True]
    worst = time.monotonic() - t0
    assert sup.breaker.state == OPEN
    assert worst < 6.0, f"3 wedged batches took {worst:.1f}s"
    # open circuit: instant CPU, no network wait at all
    t0 = time.monotonic()
    assert sup.verify_batch(_items(b"open")).all()
    assert time.monotonic() - t0 < 0.2
    # heal: probe + re-warm (reconnect) re-admits the plane
    inner.hold.clear()
    time.sleep(0.4)                           # cooldown elapses
    deadline = time.monotonic() + 10.0
    while sup.breaker.state != CLOSED and time.monotonic() < deadline:
        sup.verify_batch(_items(b"drive-%f" % time.monotonic(), n=1))
        time.sleep(0.05)
    assert sup.breaker.state == CLOSED
    tok = sup.submit_batch(_items(b"readmitted"))
    assert tok.kind == "dev"
    assert sup.collect_batch(tok).all()
    assert sup.stats["verdict_forks"] == 0
    sup.close()
