"""Per-request tracing plane: span events, flight recorder, clock anchors.

Every request (keyed by its request digest) and every 3PC batch (keyed by
the batch digest) is traced through named span events emitted at each hop
of the pipeline — client ingress, signature verdict, propagate quorum,
pre-prepare send/receive, prepare quorum, commit send, ordering, durable
flush, client reply — plus protocol ANOMALIES (suspicion raised, view
change start/complete, breaker state transitions, catchup trigger). The
correlation key is the digest the protocol already carries end to end, so
tracing needs NO wire-format change: each node records only what it saw,
and `tools/trace_report.py` assembles the per-node dumps into cross-node
latency waterfalls and pool-level critical-path attribution.

Two design constraints shape the implementation:

1. **Disabled cost is one attribute check.** Hot-path call sites guard
   every emission with `if tracer.enabled:`; `NullTracer.enabled` is a
   class attribute `False`, so a pool running untraced pays one LOAD_ATTR
   per site and never builds the event tuple. A microbenchmark assertion
   (tests/test_tracing.py) pins this below 2% of the per-txn budget.

2. **Replay determinism.** Span timestamps come ONLY from the node's
   injectable TimerService clock, and event payloads are derived from
   message content — never from wall reads — so replaying a recorded node
   under a MockTimer reproduces a byte-identical span sequence
   (tests/test_tools.py determinism guard). Wall-clock stage DURATIONS
   (apply/durable perf_counter measurements) are genuinely
   non-deterministic and therefore ride the events only when
   `wall_durations=True` (the default for live pools; replay comparisons
   construct tracers with it off).

The **flight recorder** is the bounded ring itself: the last RING_SIZE
span events + anomalies, dumped to disk automatically when an anomaly is
recorded (debounced) or on demand. Dumps are written atomically
(tmp + rename) so a crash mid-dump never leaves a torn artifact, and the
auto-dump-on-anomaly means the seconds BEFORE a crash/view-change/breaker
trip are already on disk when the postmortem starts.

The **stage clock** (`StageClock`) sits on the same span sites: one
call a site feeds the ring (timer clock, as above, when a tracer is
attached) and a wall-clock DURATION sample on the node's metrics store
(`stage.*`, perf_counter: the timer is latched once a prod cycle, so two
stages that end inside one cycle read 0 apart on it). Durations never
enter the ring. The ring holds about a second of a loaded pool; the store
holds the whole window.

Clock model: each dump carries (mono_anchor, wall_anchor, clock_domain).
In-process sims share one timer (`clock_domain="shared"`) — alignment is
the identity. TCP pools run one perf_counter epoch per process
(`clock_domain="wall"`) — the anchor pair maps each node's monotonic
times onto the wall clock, and trace_report applies a causality
refinement (a pre-prepare cannot be received before it was sent) on top.
"""
from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any, Callable, Optional

from plenum_tpu.common.metrics import (MetricsName, NullMetricsCollector,
                                       span_report)

# --- span stage names -------------------------------------------------------
# Request-keyed (key = request digest):
INGRESS = "ingress"                  # client request entered the node pipeline
AUTH = "auth"                        # signature verdict landed (data: ok)
PROPAGATE_QUORUM = "propagate_quorum"  # f+1 propagate votes -> finalized
REPLY = "reply"                      # REPLY sent to the client
# Batch-keyed (key = 3PC batch digest; data carries seq + req digests):
PP_SENT = "pp_sent"                  # primary broadcast the PRE-PREPARE
PP_RECV = "pp_recv"                  # replica admitted the PRE-PREPARE
PREPARE_QUORUM = "prepare_quorum"    # n-f matching PREPAREs
COMMIT_SENT = "commit_sent"          # own COMMIT broadcast
ORDERED = "ordered"                  # commit quorum -> Ordered emitted
APPLY = "apply"                      # uncommitted batch apply completed
# Ingress-plane (front door; request-keyed where a digest exists):
ING_ADMIT = "ing_admit"              # request admitted into its client queue
ING_SHED = "ing_shed"                # explicit load-shed reply (data: reason)
# Pool-keyed (key = ""):
ING_AUTH = "ing_auth"                # ingress auth batch dispatched (data: n, sigs)
ING_VERDICT = "ing_verdict"          # ingress auth verdicts landed (data: ok, fail)
ING_CONTROLLER = "ing_controller"    # admission-controller decision (data: knobs)
DURABLE = "durable"                  # group-commit flush closed (data: seqs)
CONTROLLER = "controller"            # batch-controller decision (data: knobs)
CRYPTO_DISPATCH = "crypto_dispatch"  # signature batch dispatched (data: kind)
READ_BATCH = "read_batch"            # read plane served a tick's queries
# fused crypto pipeline (parallel/pipeline.py): one event per resolved
# device wave — submit->pack->dispatch->collect spans (all stamped on the
# pipeline's injectable clock), plus bucket id / item count / pad waste;
# trace_report renders these as the `device` waterfall stage
DEVICE = "device"
DEVICE_CONTROLLER = "device_controller"  # pipeline-controller decision
# sharding plane (shards/): every shard-attributed span carries a
# `shard` tag in its data dict, and shard-hosted node dumps carry a
# top-level `shard` tag (Tracer(tags=...)) so trace_report can group a
# fabric's waterfalls per shard and attribute cross-shard hops
SHARD_ROUTE = "shard_route"          # router decision (data: shard, kind)
CROSS_SHARD = "cross_shard_read"     # verified cross-shard read resolved
#                                      (data: shard, ok, dur, reason)

ANOMALY_PREFIX = "anomaly."

RING_SIZE = 4096


class NullTracer:
    """Disabled tracing: `enabled` is False and every method is a no-op.
    Call sites MUST guard with `if tracer.enabled:` so the disabled path
    costs exactly one attribute check — the methods exist only for
    unguarded cold-path callers (dump plumbing, tests)."""

    enabled = False

    def emit(self, stage: str, key: str, data=None) -> None:
        pass

    def anomaly(self, kind: str, data=None) -> None:
        pass

    def snapshot(self) -> Optional[dict]:
        return None

    def dump(self, path: Optional[str] = None) -> Optional[dict]:
        return None


NULL_TRACER = NullTracer()


class Tracer(NullTracer):
    """Bounded flight-recorder ring of (t, stage, key, data) span events.

    `now` is the node's TimerService clock (sim or perf_counter) — the ONE
    time source for event stamps, keeping recorded runs replayable.
    `wall` (optional, e.g. time.time) is sampled ONCE at construction to
    anchor this node's monotonic timeline onto the wall clock for
    cross-process assembly; it never stamps individual events.
    """

    enabled = True

    def __init__(self, node: str, now: Callable[[], float],
                 ring_size: int = RING_SIZE,
                 dump_dir: Optional[str] = None,
                 clock_domain: str = "shared",
                 wall: Optional[Callable[[], float]] = None,
                 min_dump_interval: float = 5.0,
                 wall_durations: bool = True,
                 tags: Optional[dict] = None):
        self.node = node
        # free-form dump tags (e.g. {"shard": 0}); assembly-side grouping
        # only — individual events stay tag-free so hot-path cost is flat
        self.tags = dict(tags) if tags else None
        self._now = now
        self.ring: deque = deque(maxlen=ring_size)
        self.dump_dir = dump_dir
        self.clock_domain = clock_domain
        self.mono_anchor = now()
        self.wall_anchor = wall() if wall is not None else None
        self.wall_durations = wall_durations
        self.dumps_written = 0
        self.anomalies = 0
        self._min_dump_interval = min_dump_interval
        self._last_auto_dump = float("-inf")

    def emit(self, stage: str, key: str, data=None) -> None:
        self.ring.append((self._now(), stage, key, data))

    def anomaly(self, kind: str, data=None) -> None:
        """Record a protocol anomaly and auto-dump the ring (debounced):
        the last-seconds story must reach disk BEFORE whatever follows the
        anomaly (crash, wedge) can lose it."""
        self.anomalies += 1
        self.emit(ANOMALY_PREFIX + kind, "", data)
        if self.dump_dir is not None:
            now = self._now()
            if now - self._last_auto_dump >= self._min_dump_interval:
                self._last_auto_dump = now
                try:
                    self.dump()
                except OSError:
                    pass            # a full disk must not take down consensus

    def snapshot(self) -> dict:
        """The dump payload: ring contents + the clock anchors assembly
        needs. Events are JSON-ready lists; the ring itself is untouched."""
        return {
            "node": self.node,
            **({"tags": self.tags} if self.tags else {}),
            "clock_domain": self.clock_domain,
            "mono_anchor": self.mono_anchor,
            "wall_anchor": self.wall_anchor,
            "dumped_at": self._now(),
            "anomalies": self.anomalies,
            "events": [list(e) for e in self.ring],
        }

    def dump(self, path: Optional[str] = None) -> dict:
        """Write the snapshot as JSON (atomic tmp+rename — a crash mid-dump
        must never tear an artifact); -> the snapshot dict. With no path
        and no dump_dir the snapshot is only returned."""
        snap = self.snapshot()
        if path is None and self.dump_dir is not None:
            os.makedirs(self.dump_dir, exist_ok=True)
            path = os.path.join(
                self.dump_dir,
                f"{self.node}-flight-{self.dumps_written}.json")
        if path is not None:
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                # encoded in one piece (json.dump would walk the ring in
                # Python and write it chunk by chunk): an anomaly's dump
                # runs in line on the node's loop
                fh.write(json.dumps(snap, default=repr))
            os.replace(tmp, path)
            self.dumps_written += 1
        return snap


# --- the stage clock --------------------------------------------------------
# A write's stages on one node, telescoping: each ends where the next
# starts, on ONE perf_counter read. (sample name, from -> to)
STAGES = (
    MetricsName.STAGE_INBOX_WAIT,      # handed to the node -> popped
    MetricsName.STAGE_AUTH_WAIT,       # popped -> signature verdict settled
    MetricsName.STAGE_PROPAGATE_WAIT,  # verdict / first sight -> finalised
    MetricsName.STAGE_QUEUE_WAIT,      # finalised -> its PRE-PREPARE
    MetricsName.STAGE_ORDERING_WAIT,   # PRE-PREPARE -> commit quorum
    MetricsName.STAGE_COMMIT_WAIT,     # ordered -> group-commit scope closed
    MetricsName.STAGE_REPLY_WAIT,      # scope closed -> REPLY handed over
)
# in-flight bounds of the two maps the clock keeps itself (a request's
# stamps between its pop and its verdict; a batch's between its
# PRE-PREPARE and its replies): entries leave at the far site, and the
# oldest goes when a site never comes (a batch a view change dropped)
_POPPED_MAX = 8192
_BATCHES_MAX = 1024


class NullStageClock:
    """No metrics collector and no tracer: a site pays one no-op call."""

    def stamp(self):
        return None

    def arrived(self):
        return None

    def ingress(self, digest: str, frm: str, arrived=None) -> None:
        pass

    def auth(self, digest: str, ok: bool):
        return None

    def finalised(self, digest: str, state) -> None:
        pass

    def pp_sent(self, pp) -> None:
        pass

    def pp_recv(self, pp, sender: str) -> None:
        pass

    def ordered(self, key, pp, votes: int) -> None:
        pass

    def durable(self, chunk, flushed, t0: float) -> None:
        pass

    def replied(self, digest: str, state, msg) -> None:
        pass

    def retired(self, msg) -> None:
        pass

    def report(self) -> Optional[dict]:
        return None


NULL_STAGE_CLOCK = NullStageClock()


class StageClock(NullStageClock):
    """One call a span site: the ring event (timer clock, replayable, only
    when a tracer is attached) and the stage's duration on `metrics`
    (perf_counter, whole window). Per-request stamps ride the propagator's
    RequestState (`t_in`, `t_mark`, `t_sum`: freed with it); a request
    contributes a stage only where this node saw both of its ends.

    `totals` are cumulative (never flushed): name -> [count, sum], weighted
    per request. `whole` counts the requests that had every stage and sums
    the stage spans each collected on its way, as the sites measured them:
    it equals `stage.residence`'s sum unless a stamp sits in the wrong
    place."""

    def __init__(self, metrics, tracer, now: Callable[[], float],
                 clock: Callable[[], float] = time.perf_counter):
        self._metrics = metrics
        self._tracer = tracer
        self._now = now                 # the node's timer: the ring's clock
        self._clock = clock
        self.totals: dict[str, list] = {
            name: [0, 0.0] for name in STAGES + (MetricsName.STAGE_RESIDENCE,)}
        self.whole = [0, 0.0]
        # digest -> RequestState: the node points it at its propagator's
        self.states: Callable = lambda digest: None
        self._popped: dict[str, tuple] = {}
        self._batches: dict[tuple, list] = {}

    def _span(self, name: str, dur: float, weight: int = 1) -> None:
        tot = self.totals[name]
        tot[0] += weight
        tot[1] += dur * weight
        self._metrics.add_event(name, dur, weight)

    # --- request-keyed sites ------------------------------------------------

    def stamp(self) -> float:
        """A request state's first sight (propagator.Requests)."""
        return self._clock()

    def arrived(self) -> tuple:
        """handle_client_message: (perf_counter, timer time) of the append
        to the inbox, carried beside the message until it is popped."""
        return self._clock(), self._now()

    def ingress(self, digest: str, frm: str, arrived=None) -> None:
        if self._tracer.enabled:
            self._tracer.emit(INGRESS, digest, {"frm": frm} if arrived is None
                              else {"frm": frm, "queued": arrived[1]})
        if arrived is not None:
            t = self._clock()
            self._span(MetricsName.STAGE_INBOX_WAIT, t - arrived[0])
            popped = self._popped
            popped[digest] = (arrived[0], t)
            if len(popped) > _POPPED_MAX:
                del popped[next(iter(popped))]

    def auth(self, digest: str, ok: bool):
        """-> (t_in, t_mark, t_sum) for the request state this verdict
        opens (propagator.propagate), or None: no stamp from the inbox."""
        if self._tracer.enabled:
            self._tracer.emit(AUTH, digest, {"ok": bool(ok)})
        popped = self._popped.pop(digest, None)
        if popped is None:
            return None
        t = self._clock()
        self._span(MetricsName.STAGE_AUTH_WAIT, t - popped[1])
        return popped[0], t, (popped[1] - popped[0]) + (t - popped[1])

    def finalised(self, digest: str, state) -> None:
        if self._tracer.enabled:
            self._tracer.emit(PROPAGATE_QUORUM, digest,
                              {"votes": len(state.propagates)})
        if state.t_mark is not None:
            t = self._clock()
            self._span(MetricsName.STAGE_PROPAGATE_WAIT, t - state.t_mark)
            state.t_sum += t - state.t_mark
            state.t_mark = t

    # --- batch-keyed sites (master instance) -------------------------------

    def pp_sent(self, pp) -> None:
        if self._tracer.enabled:
            # reqs list links request digests -> this batch for waterfall
            # assembly; seq links the batch -> the durable flush event
            self._tracer.emit(PP_SENT, pp.digest,
                              {"seq": pp.pp_seq_no, "ledger": pp.ledger_id,
                               "reqs": list(pp.req_idr)})
        self._preprepared(pp)

    def pp_recv(self, pp, sender: str) -> None:
        if self._tracer.enabled:
            self._tracer.emit(PP_RECV, pp.digest,
                              {"seq": pp.pp_seq_no, "frm": sender,
                               "reqs": list(pp.req_idr)})
        self._preprepared(pp)

    def _preprepared(self, pp) -> None:
        t = self._clock()
        for digest in pp.req_idr:
            state = self.states(digest)
            if state is None or state.t_mark is None:
                continue            # swept, or a batch proposed again
            if state.finalised:
                self._span(MetricsName.STAGE_QUEUE_WAIT, t - state.t_mark)
                state.t_sum += t - state.t_mark
            else:
                state.t_in = None   # ordered before finalised HERE: no chain
            state.t_mark = None     # the batch's stamps take over
        # [last stamp, requests carried, ordering + commit span so far]
        batches = self._batches
        batches[(pp.view_no, pp.pp_seq_no)] = [t, len(pp.req_idr), 0.0]
        if len(batches) > _BATCHES_MAX:
            del batches[next(iter(batches))]

    def _batch_span(self, key: tuple, name: str, t: float) -> None:
        batch = self._batches.get(key)
        if batch is not None:
            self._span(name, t - batch[0], batch[1])
            batch[2] += t - batch[0]
            batch[0] = t

    def ordered(self, key, pp, votes: int) -> None:
        if self._tracer.enabled:
            self._tracer.emit(ORDERED, pp.digest,
                              {"seq": key[1], "votes": votes})
        self._batch_span(key, MetricsName.STAGE_ORDERING_WAIT, self._clock())

    def durable(self, chunk, flushed, t0: float) -> None:
        """One group-commit scope closed over `chunk` (Ordered messages);
        `flushed`: the durable stores' counter growth, or None."""
        if self._tracer.enabled:
            # batch linkage rides pp_seq_no (Ordered carries no batch
            # digest); wall duration only when the tracer allows it —
            # perf_counter deltas are not replay-deterministic
            data = {"seqs": [m.pp_seq_no for m in chunk]}
            if flushed is not None:
                data["rows"] = flushed["rows"]
                data["bytes"] = flushed["bytes"]
            if self._tracer.wall_durations:
                data["dur"] = time.perf_counter() - t0
            self._tracer.emit(DURABLE, "", data)
        t = self._clock()
        for m in chunk:
            self._batch_span((m.view_no, m.pp_seq_no),
                             MetricsName.STAGE_COMMIT_WAIT, t)

    def replied(self, digest: str, state, msg) -> None:
        if self._tracer.enabled:
            self._tracer.emit(REPLY, digest, {"seq": msg.pp_seq_no})
        batch = self._batches.get((msg.view_no, msg.pp_seq_no))
        if state.t_in is None or batch is None:
            return                  # not taken from a client by this node
        t = self._clock()
        self._span(MetricsName.STAGE_REPLY_WAIT, t - batch[0])
        self._span(MetricsName.STAGE_RESIDENCE, t - state.t_in)
        self.whole[0] += 1
        self.whole[1] += state.t_sum + batch[2] + (t - batch[0])
        state.t_in = None

    def retired(self, msg) -> None:
        """Every REPLY of the batch is out: its stamps go (an earlier
        incarnation a view change left behind goes as the oldest, at the
        bound)."""
        self._batches.pop((msg.view_no, msg.pp_seq_no), None)

    # --- reading ------------------------------------------------------------

    def report(self) -> dict:
        """VALIDATOR_INFO `stages`: cumulative count and sum per stage
        (a window's mean is a growth), quantiles from the reservoir since
        the last flush; `whole`: the requests that had every stage and the
        sum of their stage spans, to hold against `stage.residence`."""
        out = {}
        for name, (count, total) in self.totals.items():
            acc = self._metrics.accumulators.get(name)
            out[name] = span_report(
                count, total, acc.samples if acc is not None else None)
        out["whole"] = {"count": self.whole[0], "sum_s": self.whole[1]}
        return out


def unspanned(name: str, run: Callable):
    """The `span` of a service nobody handed a host-span helper to (the
    node hands node.py's `_phase` to its master's services): just run."""
    return run()


def make_stage_clock(metrics, tracer, now: Callable[[], float]):
    """On wherever the node has a metrics collector or a tracer: a
    NullMetricsCollector node without a tracer gets the shared no-op."""
    if isinstance(metrics, NullMetricsCollector) and not tracer.enabled:
        return NULL_STAGE_CLOCK
    return StageClock(metrics, tracer, now)


def make_tracer(node: str, now: Callable[[], float], config=None,
                dump_dir: Optional[str] = None,
                clock_domain: str = "shared",
                wall: Optional[Callable[[], float]] = None):
    """Config-gated construction seam: FLIGHT_RECORDER=False -> the shared
    NullTracer (one attribute check per hot-path site, zero allocations)."""
    if config is not None and not getattr(config, "FLIGHT_RECORDER", True):
        return NULL_TRACER
    ring = getattr(config, "TRACE_RING_SIZE", RING_SIZE) if config else RING_SIZE
    interval = getattr(config, "FLIGHT_DUMP_MIN_INTERVAL", 5.0) \
        if config else 5.0
    return Tracer(node, now, ring_size=ring, dump_dir=dump_dir,
                  clock_domain=clock_domain, wall=wall,
                  min_dump_interval=interval)


def span_sequence(snapshot: Optional[dict]) -> bytes:
    """Canonical byte serialization of a snapshot's span sequence — the
    unit the record/replay determinism guard compares byte-for-byte."""
    if snapshot is None:
        return b""
    return json.dumps(snapshot["events"], sort_keys=True,
                      separators=(",", ":"), default=repr).encode()
