"""Self-healing supervisor for the device crypto plane.

Round 5 proved the weakest link in the offload story is the plane
itself: the device went dark mid-round and every node on a
device-backed verifier stalled the full flat 300 s socket timeout per
batch before falling back — call after call. Committee-BFT systems live
or die on the tail latency of exactly this verification path
(arXiv:2302.00418), and accelerator-consensus work (VaultxGPU,
arXiv:2606.14007) shows offload only wins when host fallback is
seamless: a wedged accelerator must degrade a node, never wedge the
pool.

This module wraps ANY device-backed `Ed25519Verifier` (JaxEd25519Verifier,
ShardedJaxEd25519Verifier, the service:* client) with three mechanisms:

1. **Circuit breaker** — K consecutive failures/deadline-misses OPEN the
   circuit: all dispatch routes to the CPU verifier instantly. After a
   cooldown the breaker goes HALF-OPEN and a *probe* batch (one known-good
   + one known-bad signature at a compiled shape) is dispatched to the
   device — real traffic keeps flowing on CPU meanwhile. The device is
   re-admitted only after a successful **re-warm** (key-cache re-upload /
   reconnect via the inner's `rewarm()` hook) AND a correct probe verdict.
   Hysteresis: every re-open doubles the cooldown (capped), decaying back
   to the base only after a long run of closed-state successes — a
   flapping device cannot thrash the pool with probe storms.

2. **Adaptive deadlines + hedged dispatch** — every device dispatch gets
   a budget derived from batch size and a rolling p99 of observed
   per-item device latency (clamped; generous before the first success so
   multi-minute XLA compiles still fit). When a dispatch overruns its
   budget, a CPU verification of the same items runs and its verdict is
   taken — the *hedge*. Verdicts are pure functions of content (both
   backends share `_precheck`, and the verdict caches are content-keyed),
   so hedging can never fork backend verdicts; a late device result is
   still reaped and compared, and any mismatch is counted loudly
   (`verdict_forks` — an invariant violation, asserted zero in tests).

3. **Bounded in-flight queueing with backpressure** — outstanding device
   bytes are tracked against a watermark; past it, new batches go straight
   to CPU instead of queueing behind a slow device.

Everything is observable: breaker state/transitions, fallback counts,
hedge wins, deadline misses, and the dispatch-budget distribution are
exposed via `supervisor_stats()` and flushed as node metrics
(common/metrics.py CRYPTO_* names -> tools.metrics_report -> bench line).

The clock is injectable (`set_clock`) so the deterministic sim harness
(MockTimer pools, the `device_flap` fuzz scenario) drives the whole state
machine on simulated time.
"""
from __future__ import annotations

import collections
import os
import time
from typing import Optional, Sequence

import numpy as np

from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier, Ed25519Signer,
                                       Ed25519Verifier, VerifyItem)

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

STATE_CODE = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}

# a submit_batch that blocks this long (real seconds) is compiling, not
# dispatching: the deadline clock starts when it returns (submit_batch)
_BLOCKING_SUBMIT_S = 1.0


class CircuitBreaker:
    """Consecutive-failure breaker with flap hysteresis.

    closed --K failures--> open --cooldown--> half_open --probe ok--> closed
                              ^                  |
                              +---probe failed---+  (cooldown doubles)

    The breaker itself never dispatches anything: the supervisor asks
    `probe_due()` and reports probe outcomes via `close()` / `reopen()`.
    Cooldown doubles on every open (capped) and decays back to the base
    only after `reset_after` consecutive closed-state successes, so a
    device that heals just long enough to pass one probe and wedges again
    faces exponentially rarer probes, not a thrash loop.
    """

    def __init__(self, fail_threshold: int = 3, cooldown: float = 2.0,
                 cooldown_max: float = 60.0, reset_after: int = 64,
                 now=None):
        self.fail_threshold = max(1, fail_threshold)
        self._cooldown_base = cooldown
        self.cooldown = cooldown
        self.cooldown_max = cooldown_max
        self.reset_after = reset_after
        self._now = now or time.monotonic
        self.state = CLOSED
        self._consecutive_failures = 0
        self._successes_since_close = 0
        self._opened_at: Optional[float] = None
        # set on every open, cleared only by the reset_after decay: any
        # open while set is a RE-open (a flap) and doubles the cooldown
        self._flap_guard = False
        self.opens = 0
        self.closes = 0
        self.probes = 0
        # optional observer called as on_transition(old_state, new_state)
        # on every breaker state change — the tracing plane records these
        # as flight-recorder anomalies (common/tracing.py); must never
        # raise into the dispatch path
        self.on_transition = None

    def set_clock(self, now) -> None:
        self._now = now

    @property
    def state_code(self) -> int:
        return STATE_CODE[self.state]

    def record_success(self) -> None:
        if self.state != CLOSED:
            # a straggler landing while open proves nothing about the
            # device NOW; only a probe + re-warm re-admits it
            return
        self._consecutive_failures = 0
        self._successes_since_close += 1
        if self._successes_since_close >= self.reset_after:
            self.cooldown = self._cooldown_base   # hysteresis decays
            self._flap_guard = False

    def record_failure(self) -> bool:
        """-> True if this failure opened the circuit."""
        if self.state == OPEN:
            return False
        if self.state == HALF_OPEN:
            self.reopen()
            return True
        self._consecutive_failures += 1
        if self._consecutive_failures >= self.fail_threshold:
            self._open()
            return True
        return False

    def _open(self) -> None:
        if self._flap_guard:
            # re-opening before the decay window passed: a flap — probes
            # get exponentially rarer, capped
            self.cooldown = min(self.cooldown * 2, self.cooldown_max)
        self._flap_guard = True
        self._transition(OPEN)
        self.opens += 1
        self._opened_at = self._now()
        self._successes_since_close = 0

    def probe_due(self) -> bool:
        return (self.state == OPEN and self._opened_at is not None
                and self._now() - self._opened_at >= self.cooldown)

    def to_half_open(self) -> None:
        self._transition(HALF_OPEN)
        self.probes += 1

    def reopen(self) -> None:
        """Probe failed (or a failure landed while half-open): back to
        OPEN; _open doubles the cooldown via the flap guard."""
        self._open()

    def close(self) -> None:
        """Probe + re-warm succeeded: re-admit the device."""
        self._transition(CLOSED)
        self.closes += 1
        self._consecutive_failures = 0
        self._successes_since_close = 0
        self._opened_at = None

    def _transition(self, new_state: str) -> None:
        old, self.state = self.state, new_state
        if self.on_transition is not None and old != new_state:
            try:
                self.on_transition(old, new_state)
            except Exception:
                pass        # an observer bug must not wedge dispatch


class DeadlineBudget:
    """Per-dispatch deadline = base + n_items * p99(per-item device cost)
    * margin, clamped to [min_s, ceiling]. The ceiling is `cold_max`
    until the first successful dispatch lands (a remote plane may sit
    behind its own first XLA compile, which legitimately takes minutes)
    and `warm_max` afterwards — a wedged device then costs one bounded
    miss, never a multi-minute stall per batch. A LOCAL compile never
    counts against the budget at all: the supervisor starts the clock
    when a blocking submit returns (SupervisedVerifier.submit_batch)."""

    def __init__(self, base: float = 0.5, per_item_initial: float = 0.02,
                 margin: float = 8.0, min_s: float = 0.25,
                 warm_max: float = 30.0, cold_max: float = 300.0,
                 window: int = 256):
        self.base = base
        self.per_item_initial = per_item_initial
        self.margin = margin
        self.min_s = min_s
        self.warm_max = warm_max
        self.cold_max = cold_max
        self.warmed = False
        self._samples: collections.deque = collections.deque(maxlen=window)

    def per_item_p99(self) -> float:
        if not self._samples:
            return self.per_item_initial
        from plenum_tpu.common.metrics import percentile
        return percentile(self._samples, 0.99)

    def budget(self, n_items: int) -> float:
        ceiling = self.warm_max if self.warmed else self.cold_max
        raw = self.base + n_items * self.per_item_p99() * self.margin
        return max(self.min_s, min(raw, ceiling))

    def record(self, n_items: int, elapsed: float) -> None:
        self._samples.append(elapsed / max(1, n_items))
        self.warmed = True


class _SupToken:
    __slots__ = ("kind", "inner", "items", "t0", "t_sub", "deadline",
                 "nbytes", "verdicts", "budget")

    def __init__(self, kind, inner=None, items=None, t0=0.0, deadline=0.0,
                 nbytes=0, verdicts=None, budget=0.0, t_sub=None):
        self.kind = kind            # "dev" | "cpu"
        self.inner = inner
        self.items = items
        self.t0 = t0                # before the submit: stall accounting
        # t0 moved past a BLOCKING submit (a compile): the origin of the
        # deadline and of the per-item latency sample judged against it
        self.t_sub = t0 if t_sub is None else t_sub
        self.deadline = deadline
        self.nbytes = nbytes
        self.verdicts = verdicts
        self.budget = budget

    @property
    def lanes(self) -> Optional[int]:
        """Padded rows of the device dispatch behind this token; None
        where none was made and the CPU answered."""
        return getattr(self.inner, "lanes", None)


def _item_bytes(items: Sequence[VerifyItem]) -> int:
    total = 0
    for it in items:
        try:
            total += len(it[0]) + len(it[1]) + len(it[2])
        except Exception:
            total += 128      # malformed entries still occupy queue space
    return total


class SupervisedVerifier(Ed25519Verifier):
    """Breaker + adaptive-deadline + hedged-fallback wrapper around a
    device-backed verifier. Implements the same submit/collect token
    protocol, so node pipelining and the ring work unchanged on top of
    it. "Device" includes REMOTE backends: the
    federated pipeline (parallel/federation.py) wraps each rostered
    crypto host's service client in its own supervisor, so a dead host
    opens exactly that lane's breaker and the probe's `rewarm()` hook —
    the client's reconnect — re-admits the host when it returns."""

    _PROBE_SEED = b"plane-probe-signer".ljust(32, b"\0")

    def __init__(self, device: Ed25519Verifier,
                 fallback: Optional[Ed25519Verifier] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 budget: Optional[DeadlineBudget] = None,
                 max_outstanding_bytes: int = 8 * 1024 * 1024,
                 now=None, label: str = ""):
        self._device = device
        # which backend this supervisor guards — the multi-device
        # pipeline labels one supervisor per chip lane ("lane0", ...)
        # so breaker stories in stats/telemetry name the sick chip
        self.label = label
        self._fallback = fallback or CpuEd25519Verifier()
        self._now = now or time.monotonic
        self.breaker = breaker or CircuitBreaker(now=self._now)
        self.budget = budget or DeadlineBudget()
        self.max_outstanding_bytes = max_outstanding_bytes
        self._outstanding_bytes = 0
        # hedged dispatches whose device verdict has not landed yet: kept
        # (bounded by _MAX_ZOMBIES, with explicit discard on eviction so
        # the device/client can drop its reply state) so a late result is
        # compared against the hedge — the no-fork invariant is OBSERVED,
        # not assumed
        self._MAX_ZOMBIES = 64
        self._zombies: collections.deque = collections.deque()
        self._probe: Optional[_SupToken] = None
        self._probe_signer = Ed25519Signer(seed=self._PROBE_SEED)
        self._probe_nonce = 0
        # budget values chosen per dispatch, drained by the metrics
        # sampler into the flushed deadline distribution
        self._budget_samples: list[float] = []
        self.stats = {
            "device_batches": 0, "device_items": 0,
            "fallback_batches": 0, "fallback_items": 0,
            "open_circuit_fallbacks": 0, "backpressure_fallbacks": 0,
            "device_errors": 0, "deadline_misses": 0, "hedge_wins": 0,
            "late_landings": 0, "verdict_forks": 0,
            "probes_started": 0, "probe_failures": 0, "rewarms": 0,
            "max_stall_s": 0.0, "max_budget_s": 0.0,
        }

    # --- clock plumbing (deterministic sims drive the state machine) ----

    def set_clock(self, now) -> None:
        self._now = now
        self.breaker.set_clock(now)

    # --- probe / re-warm state machine ----------------------------------

    def _probe_items(self) -> tuple[list[VerifyItem], list[bool]]:
        """One known-good + one known-bad signature. The nonce makes the
        content fresh per probe so no verdict cache can satisfy it — the
        probe must exercise the actual device round-trip."""
        self._probe_nonce += 1
        msg = b"plane-probe-%d" % self._probe_nonce
        sig = self._probe_signer.sign(msg)
        vk = self._probe_signer.verkey
        bad_msg = b"plane-probe-bad-%d" % self._probe_nonce
        return [(msg, sig, vk), (bad_msg, sig, vk)], [True, False]

    def _start_probe(self) -> None:
        self.breaker.to_half_open()
        self.stats["probes_started"] += 1
        # RE-WARM FIRST: reconnect / re-upload the key cache before any
        # probe bytes move — re-admission without a re-warm would hand
        # real traffic to a device whose session state died with the wedge
        rewarm = getattr(self._device, "rewarm", None)
        if callable(rewarm):
            try:
                rewarm()
                self.stats["rewarms"] += 1
            except Exception:
                self.stats["probe_failures"] += 1
                self.breaker.reopen()
                return
        items, expected = self._probe_items()
        t0 = self._now()
        try:
            inner = self._device.submit_batch(items)
        except Exception:
            self.stats["probe_failures"] += 1
            self.breaker.reopen()
            return
        self._probe = _SupToken("dev", inner, items, t0,
                                t0 + self.budget.budget(len(items)),
                                verdicts=expected)

    def _service_probe(self) -> None:
        """Advance breaker recovery: start a probe when the cooldown
        expires, poll the in-flight one. Runs at every submit/collect, so
        fallback-mode traffic itself drives re-admission."""
        if self._probe is None:
            if self.breaker.probe_due():
                self._start_probe()
            return
        tok = self._probe
        try:
            got = self._device.collect_batch(tok.inner, wait=False)
        except Exception:
            got = False            # sentinel: errored
        if got is None:
            if self._now() >= tok.deadline:
                self._probe = None
                self.stats["probe_failures"] += 1
                self.breaker.reopen()
            return
        self._probe = None
        if got is not False and list(np.asarray(got, dtype=bool)) == \
                list(tok.verdicts):
            self.budget.record(len(tok.items), self._now() - tok.t0)
            self.breaker.close()
        else:
            self.stats["probe_failures"] += 1
            self.breaker.reopen()

    def pump_recovery(self) -> None:
        """Drive breaker recovery WITHOUT traffic. `_service_probe` runs
        on the submit/collect path, which assumes a degraded verifier
        still sees batches — true for pinned lanes, false for a dead
        federated host the pipeline's placement routes around entirely.
        The ring pump calls this on idle open lanes so such a host can
        rejoin on its own."""
        if self.breaker.state != CLOSED:
            self._service_probe()

    # --- zombie reaping (late device results after a hedge) -------------

    def _reap_zombies(self) -> None:
        now = self._now()
        keep = []
        for tok in self._zombies:
            try:
                got = self._device.collect_batch(tok.inner, wait=False)
            except Exception:
                self._discard(tok)
                continue
            if got is None:
                if now - tok.t0 < 20 * max(tok.budget, 1.0):
                    keep.append(tok)
                else:
                    self._discard(tok)
                continue
            self.stats["late_landings"] += 1
            if not np.array_equal(np.asarray(got, dtype=bool),
                                  np.asarray(tok.verdicts, dtype=bool)):
                # should be impossible: both backends share _precheck and
                # verdicts are pure functions of content. Count loudly.
                self.stats["verdict_forks"] += 1
        self._zombies.clear()
        self._zombies.extend(keep)

    def _discard(self, tok: _SupToken) -> None:
        discard = getattr(self._device, "discard", None)
        if callable(discard):
            try:
                discard(tok.inner)
            except Exception:
                pass

    # --- fallback + hedging ---------------------------------------------

    def _cpu_token(self, items, counter: Optional[str]) -> _SupToken:
        self.stats["fallback_batches"] += 1
        self.stats["fallback_items"] += len(items)
        if counter:
            self.stats[counter] += 1
        return _SupToken("cpu",
                         verdicts=self._fallback.verify_batch(items))

    def _note_stall(self, tok: _SupToken) -> None:
        stall = self._now() - tok.t0
        if stall > self.stats["max_stall_s"]:
            self.stats["max_stall_s"] = stall

    def _hedge(self, tok: _SupToken):
        """Deadline overrun: race the CPU on the same items and take its
        verdict. The device token is kept for late-landing comparison."""
        self.stats["deadline_misses"] += 1
        self.breaker.record_failure()
        verdicts = self._fallback.verify_batch(tok.items)
        self.stats["hedge_wins"] += 1
        self.stats["fallback_batches"] += 1
        self.stats["fallback_items"] += len(tok.items)
        self._outstanding_bytes -= tok.nbytes
        self._note_stall(tok)
        tok.verdicts = verdicts
        zombie = _SupToken("dev", tok.inner, tok.items, tok.t0,
                           tok.deadline, verdicts=verdicts,
                           budget=tok.budget)
        # bounded WITH explicit discard: silently evicting would strand
        # the abandoned request's reply state inside the device client
        while len(self._zombies) >= self._MAX_ZOMBIES:
            self._discard(self._zombies.popleft())
        self._zombies.append(zombie)
        return verdicts

    def _device_failed(self, tok: _SupToken):
        self.stats["device_errors"] += 1
        self.breaker.record_failure()
        self._outstanding_bytes -= tok.nbytes
        self._note_stall(tok)
        verdicts = self._fallback.verify_batch(tok.items)
        self.stats["fallback_batches"] += 1
        self.stats["fallback_items"] += len(tok.items)
        tok.verdicts = verdicts
        return verdicts

    # --- Ed25519Verifier protocol ---------------------------------------

    def preload(self, waves) -> list:
        """Warm-up only, and unsupervised on purpose: a program that
        cannot be obtained must raise there, not fall back to the CPU."""
        return self._device.preload(waves)

    def submit_batch(self, items: Sequence[VerifyItem]):
        items = list(items)
        self._service_probe()
        self._reap_zombies()
        if not items:
            return _SupToken("cpu", verdicts=np.zeros(0, dtype=bool))
        if self.breaker.state != CLOSED:
            return self._cpu_token(items, "open_circuit_fallbacks")
        nbytes = _item_bytes(items)
        if self._outstanding_bytes + nbytes > self.max_outstanding_bytes \
                and self._outstanding_bytes > 0:
            return self._cpu_token(items, "backpressure_fallbacks")
        t0 = self._now()
        r0 = time.monotonic()
        try:
            inner = self._device.submit_batch(items)
        except Exception:
            self.stats["device_errors"] += 1
            self.breaker.record_failure()
            return self._cpu_token(items, None)
        blocked = time.monotonic() - r0
        budget = self.budget.budget(len(items))
        self._budget_samples.append(budget)
        if len(self._budget_samples) > 4096:
            del self._budget_samples[:2048]
        if budget > self.stats["max_budget_s"]:
            self.stats["max_budget_s"] = budget
        self._outstanding_bytes += nbytes
        self.stats["device_batches"] += 1
        self.stats["device_items"] += len(items)
        # The deadline clock starts when a BLOCKING submit returns. The
        # first dispatch at a new shape traces and compiles synchronously
        # inside submit_batch (tens of seconds to minutes), and time
        # already spent there cannot be hedged away: counted against a
        # budget sized for device latency, it made the first collect
        # after EVERY cold compile a deadline miss — hedged on the CPU and
        # booked against the breaker. The span is measured on the REAL
        # clock because a node's injected clock is latched once per prod
        # cycle and cannot see time pass inside one; it is honoured only
        # past _BLOCKING_SUBMIT_S, so an ordinary submit (host packing,
        # a socket send) leaves deadlines and replayed sims exactly as
        # they were. A device that stops answering is still hedged one
        # budget after its work was enqueued.
        t_sub = t0 + blocked if blocked >= _BLOCKING_SUBMIT_S else t0
        return _SupToken("dev", inner, items, t0, t_sub + budget,
                         nbytes=nbytes, budget=budget, t_sub=t_sub)

    def collect_batch(self, token, wait: bool = True):
        self._service_probe()
        if token.kind == "cpu" or token.verdicts is not None:
            return token.verdicts
        try:
            got = self._device.collect_batch(token.inner, wait=False)
        except Exception:
            return self._device_failed(token)
        if got is not None:
            self._outstanding_bytes -= token.nbytes
            elapsed = max(0.0, self._now() - token.t_sub)
            self.budget.record(len(token.items), elapsed)
            self.breaker.record_success()
            self._note_stall(token)
            token.verdicts = np.asarray(got, dtype=bool)
            return token.verdicts
        now = self._now()
        if now >= token.deadline:
            return self._hedge(token)
        if not wait:
            return None
        # Blocking collect: poll non-blocking under a REAL-time bound so
        # a frozen injected clock (sim) cannot spin forever; the budget
        # math stays on the injected clock.
        real_deadline = time.monotonic() + max(0.0, token.deadline - now)
        while time.monotonic() < real_deadline:
            try:
                got = self._device.collect_batch(token.inner, wait=False)
            except Exception:
                return self._device_failed(token)
            if got is not None:
                self._outstanding_bytes -= token.nbytes
                self.budget.record(len(token.items),
                                   max(0.0, self._now() - token.t_sub))
                self.breaker.record_success()
                self._note_stall(token)
                token.verdicts = np.asarray(got, dtype=bool)
                return token.verdicts
            if self._now() >= token.deadline:
                break
            time.sleep(0.001)
        return self._hedge(token)

    def verify_batch(self, items: Sequence[VerifyItem]) -> np.ndarray:
        return self.collect_batch(self.submit_batch(items), wait=True)

    # --- observability ---------------------------------------------------

    def drain_budget_samples(self) -> list[float]:
        out, self._budget_samples = self._budget_samples, []
        return out

    def supervisor_stats(self) -> dict:
        return dict(self.stats,
                    **({"label": self.label} if self.label else {}),
                    breaker_state=self.breaker.state,
                    breaker_state_code=self.breaker.state_code,
                    breaker_opens=self.breaker.opens,
                    breaker_closes=self.breaker.closes,
                    breaker_cooldown_s=self.breaker.cooldown,
                    outstanding_bytes=self._outstanding_bytes,
                    budget_warmed=self.budget.warmed,
                    per_item_p99_s=self.budget.per_item_p99())

    def close(self) -> None:
        for obj in (self._device, self._fallback):
            fn = getattr(obj, "close", None)
            if callable(fn):
                try:
                    fn()
                except Exception:
                    pass

    def __getattr__(self, name):
        # delegate non-protocol attributes (dispatches, socket_path, ...)
        # to the device verifier; internals are never proxied so chain
        # walkers (find_supervisor) cannot wander into the device
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.__dict__["_device"], name)


# `supervisor_stats()` counters that grow exactly when a batch did NOT get
# its verdict from a healthy device dispatch (or the no-fork invariant
# broke). Warm-up, the pool runners' backend_state and chip_smoke.py all
# judge "the device did the work" by these not moving.
FALLBACK_COUNTERS = ("fallback_batches", "hedge_wins", "deadline_misses",
                     "device_errors", "open_circuit_fallbacks",
                     "backpressure_fallbacks", "verdict_forks",
                     "breaker_opens")


def fallback_growth(before: dict, after: dict) -> dict:
    """{counter: delta} for every FALLBACK_COUNTERS entry that grew
    between two `supervisor_stats()` snapshots — empty means every batch
    in between was answered by the device."""
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in FALLBACK_COUNTERS
            if after.get(k, 0) > before.get(k, 0)}


def supervise(device: Ed25519Verifier, **kwargs) -> SupervisedVerifier:
    """Wrap a device-backed verifier in the plane supervisor. The ops
    escape hatch PLENUM_CRYPTO_SUPERVISOR=0 returns the device bare."""
    if os.environ.get("PLENUM_CRYPTO_SUPERVISOR", "1") == "0":
        return device
    return SupervisedVerifier(device, **kwargs)


def find_supervisor(verifier) -> Optional[SupervisedVerifier]:
    """Locate the SupervisedVerifier inside a chain of wrappers that
    each hold the next as `_inner`; used by the node's metric sampler."""
    seen = 0
    obj = verifier
    while obj is not None and seen < 4:
        if isinstance(obj, SupervisedVerifier):
            return obj
        obj = obj.__dict__.get("_inner") if hasattr(obj, "__dict__") else None
        seen += 1
    return None
