"""Layered configuration.

Reference behavior: plenum/config.py (module-level tunables) merged by
common/config_util.py:getConfig with /etc + network + user overrides. Here the
defaults live on a dataclass; `load_config` layers dict overrides on top, and
strategy classes remain injectable by reference (SURVEY.md §5 config system).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass
class Config:
    # --- 3PC batching (ref plenum/config.py:256-258) ---
    Max3PCBatchSize: int = 1000
    # the LONGEST a queued request waits for its batch, behind a batch of
    # its instance still being ordered; an instance with nothing in flight
    # cuts at once (ordering_service._cut_reason). Ref default 3s.
    Max3PCBatchWait: float = 0.1
    # Deep in-flight window: how far the primary's speculative uncommitted
    # batches may run AHEAD of the last committed one before fresh cuts
    # pause (still clamped by the [low, low+LOG_SIZE] watermark window and
    # reverted wholesale on view change). The reference pinned this at 4,
    # which made every slow commit stall all fresh cuts; the batch
    # controller steers the EFFECTIVE depth within [4, this] at runtime.
    Max3PCBatchesInFlight: int = 64

    # --- closed-loop batch controller (consensus/batch_controller.py) ---
    # AIMD steering of batch size / partial-batch wait / in-flight depth /
    # group-commit coalescing from rolling per-stage latency attribution
    # (queue wait, 3PC span, durable flush — all stamped on the injectable
    # timer) toward the latency SLO below. False freezes every knob at its
    # static config value.
    BATCH_CONTROLLER: bool = True
    # p95 latency target (seconds) for the SUM of the controller's three
    # attributed stages: oldest-request queue wait at cut + cut->commit-
    # quorum span + durable-flush span (each p95 taken over its own
    # rolling window — a conservative, pipelining-agnostic bound on a
    # request's batch-path latency, NOT a single batch's cut->flush
    # measurement; note the queue stage deliberately contains the batch
    # wait itself, so the SLO must comfortably exceed BATCH_WAIT_MAX)
    BATCH_SLO_P95: float = 0.5
    # decision cadence on the node timer (seconds)
    BATCH_CONTROL_INTERVAL: float = 0.5
    # bounds the controller roams within: Max3PCBatchWait is the STARTING
    # wait; the controller may grow it to BATCH_WAIT_MAX when per-batch
    # fixed costs dominate (coalesce harder) or shrink it to BATCH_WAIT_MIN
    # when queueing dominates. Max3PCBatchSize stays the hard size cap.
    BATCH_WAIT_MIN: float = 0.005
    # half the SLO: a fully-grown wait must not trip the SLO by itself
    # (the queue stage contains the deliberate batch wait)
    BATCH_WAIT_MAX: float = 0.25
    BATCH_SIZE_MIN: int = 16
    # how many ready Ordered batches may coalesce under ONE group-commit
    # scope per drain — the hard cap; the controller starts at min(8, cap)
    # and steers within [that, this] (+4 when flush amortization pays,
    # −1 decay under headroom). Deep pipelines can stack dozens of ready
    # batches, and an unbounded scope would put every earlier batch's
    # REPLY behind the whole stack's flush.
    GROUP_COMMIT_MAX_BATCHES: int = 32

    # --- checkpoints / watermarks (ref config.py:273-276) ---
    CHK_FREQ: int = 100
    LOG_SIZE: int = 300

    # --- monitor / RBFT degradation (ref config.py:140-154) ---
    DELTA: float = 0.1                  # master throughput ratio floor
    OMEGA: float = 20.0                 # latency excess threshold
    PerfCheckFreq: float = 10.0

    # --- notifier events (ref notifierEventTriggeringConfig
    #     config.py:165-184 + SpikeEventsEnabled) ---
    NOTIFIER_EVENTS_ENABLED: bool = True
    NOTIFIER_SPIKE_BOUNDS_COEFF: float = 10.0
    NOTIFIER_SPIKE_MIN_CNT: int = 15
    NOTIFIER_SPIKE_MIN_ACTIVITY: float = 10.0
    throughput_first_ts_window: float = 15.0

    # --- receive quotas (ref config.py:250-251) ---
    LISTENER_MESSAGE_QUOTA: int = 100
    REMOTES_MESSAGE_QUOTA: int = 100

    # --- client connection budget (ref config.py:285-292) ---
    MAX_CONNECTED_CLIENTS: int = 400
    CLIENT_CONN_IDLE_TIMEOUT: float = 300.0

    # --- process GC cadence (see common/metrics.tune_gc_for_server) ---
    GC_SERVER_TUNING: bool = True

    # --- view change (ref config.py:294-295) ---
    NEW_VIEW_TIMEOUT: float = 30.0
    INSTANCE_CHANGE_TIMEOUT: float = 120.0

    # --- freshness (ref config.py:263) ---
    STATE_FRESHNESS_UPDATE_INTERVAL: float = 300.0

    # --- primary health watchdog (ref primary_connection_monitor_service +
    #     unordered-request checks, monitor.py:425) ---
    PRIMARY_HEALTH_CHECK_FREQ: float = 5.0
    ORDERING_PROGRESS_TIMEOUT: float = 30.0
    # vote within seconds of LOSING THE CONNECTION to the primary, without
    # waiting out the (much longer) ordering-stall / freshness windows
    # (ref ToleratePrimaryDisconnection config.py:184 + primary_connection_
    # monitor_service.py)
    # how long a lost primary connection must persist before this node's
    # InstanceChange vote (ref ToleratePrimaryDisconnection = 60s!). The
    # dialer's retry backoff tops out at 1.0s (tcp_stack.RETRY_MAX), so a
    # transient drop re-establishes within at most one full backoff plus
    # a handshake — comfortably inside this window; and a premature lone
    # vote is harmless anyway (starting a view change needs a strong
    # quorum of votes). 1.5s halves the measured crash-recovery stall
    # (the detect->vote wait dominates it; see docs/performance.md
    # view-change stall decomposition).
    PRIMARY_DISCONNECT_TIMEOUT: float = 1.5

    # --- faulty backup instances (ref backup_instance_faulty_processor +
    #     ReplicasRemovingWithDegradation config) ---
    BACKUP_INSTANCE_FAULTY_CHECK_FREQ: float = 10.0
    # straggler self-check cadence: a node whose master ordering shows a
    # commit QUORUM ahead of a position that made no progress across one
    # full interval resyncs via catchup (below CHK_FREQ there is no
    # checkpoint-lag signal, and its lone IC vote can't reach quorum)
    STUCK_BEHIND_CHECK_FREQ: float = 5.0
    BACKUP_INSTANCE_FAULTY_TIMEOUT: float = 60.0

    # --- WAN-degraded retry/timeout hardening (common/backoff.py;
    #     docs/robustness.md "Degraded WAN and membership churn") ---
    # catchup re-requests pace on srtt+4*rttvar (RFC 6298 shape) instead
    # of the flat 5 s timer, with jittered exponential backoff between
    # fruitless retries; False restores the flat timer everywhere
    CATCHUP_ADAPTIVE_TIMEOUTS: bool = True
    CATCHUP_RETRY_MIN: float = 0.25
    CATCHUP_RETRY_MAX: float = 30.0
    # node-level catchup progress watchdog: a catchup whose progress key
    # is frozen across one interval gets kicked (forced provider rotation
    # + immediate re-request); repeated kicks escalate to a full restart
    # of the catchup round
    CATCHUP_WATCHDOG_INTERVAL: float = 5.0
    CATCHUP_WATCHDOG_RESTART_KICKS: int = 3
    # graceful degradation: after this many catchup rounds ending in
    # divergence (committed prefix conflicts with the quorum target) the
    # node stops retrying, stays OUT of ordering, and keeps serving
    # verified reads at its last anchored root (read-only degraded mode)
    CATCHUP_MAX_DIVERGED_ROUNDS: int = 2
    # view-change escalation timeout stretches (never shrinks) with the
    # measured RTT: timeout = clamp(NEW_VIEW_TIMEOUT, mult*rto, MAX)
    VC_ADAPTIVE_TIMEOUTS: bool = True
    VC_RTT_TIMEOUT_MULT: float = 20.0
    VC_TIMEOUT_MAX: float = 120.0
    # view-change storm self-check: this many consecutive view-change
    # STARTS without one completing suggests the pool disagrees on
    # something a view change cannot fix — typically a registry split
    # (a membership txn committed on some validators but not others, so
    # primary selection diverges and NO view can gather a NEW_VIEW
    # quorum). Resync the pool ledger instead of escalating forever.
    VC_STORM_RESYNC_STARTS: int = 3

    # --- metrics (ref config.py METRICS_COLLECTOR_TYPE/flush) ---
    METRICS_FLUSH_INTERVAL: float = 10.0
    QUEUE_GAUGE_SAMPLE_INTERVAL: float = 1.0

    # --- tracing / flight recorder (common/tracing.py) ---
    # False drops the node to the NullTracer fast path (one attribute
    # check per span site, zero allocations — the <=2% TPS budget)
    FLIGHT_RECORDER: bool = True
    TRACE_RING_SIZE: int = 4096
    # anomaly auto-dumps are debounced to at most one per this interval
    FLIGHT_DUMP_MIN_INTERVAL: float = 5.0

    # --- live fleet telemetry plane (observability/) ---
    # False drops the node to the NULL_TELEMETRY fast path (one attribute
    # check per call site, no snapshot timer registered — the <=2% budget
    # twin of FLIGHT_RECORDER=False, pinned by the same microbench style)
    TELEMETRY: bool = True
    # snapshot cadence on the node's injectable timer (seconds); every
    # stamp in a snapshot rides this clock, so a recorded run replays a
    # byte-identical snapshot stream
    TELEMETRY_INTERVAL: float = 1.0
    # bounded local history of recent snapshots held in memory (the
    # aggregator and console read these; the ring is the memory bound)
    TELEMETRY_RING: int = 256
    # on-disk spool: snapshots rotate over this many numbered files
    # (atomic tmp+rename, same discipline as flight dumps); 0 disables
    TELEMETRY_SPOOL_MAX: int = 64
    # name of the peer hosting the pool's FleetAggregator: when set,
    # every OTHER node ships its snapshots there as the best-effort
    # TELEMETRY wire message (Node.ship_telemetry_to); empty = spool/
    # in-process sinks only
    TELEMETRY_SHIP_TO: str = ""
    # a node silent for longer than this (vs the newest snapshot the
    # aggregator has seen from anyone) scores health 0.0: crashed or
    # partitioned must read as DOWN, never frozen-at-last-healthy
    TELEMETRY_STALE_AFTER: float = 10.0
    # multi-window SLO burn-rate alerting (observability/aggregator.py):
    # burn = (violating fraction) / SLO_BURN_BUDGET per window; the alert
    # fires only when BOTH windows burn past SLO_BURN_THRESHOLD — the
    # fast window for recency, the slow one so a blip cannot page
    SLO_BURN_FAST_WINDOW: float = 10.0
    SLO_BURN_SLOW_WINDOW: float = 60.0
    SLO_BURN_BUDGET: float = 0.05       # tolerated SLO-violation fraction
    SLO_BURN_THRESHOLD: float = 2.0     # burn multiple that raises the alert
    # per-client-cap sheds burn the ingress SLO budget only when at
    # least this many DISTINCT clients were capped in one snapshot
    # interval (breadth = pool overload; below it, fairness limiting a
    # few abusers must not page)
    INGRESS_SLO_CAP_BREADTH: int = 3
    # per-node health score alert floor + the shard load-imbalance index
    # (max shard rate / mean shard rate) past which the hot shard is
    # flagged — the exact signal live split/merge will consume
    HEALTH_ALERT_FLOOR: float = 0.5
    SHARD_IMBALANCE_THRESHOLD: float = 1.5
    # --- fleet history plane (observability/history.py) ---
    # the aggregator appends one compact fleet row per pool interval to
    # a HistoryRecorder ring; rows rotate over this many on-disk slots
    # (tmp+rename, the telemetry-spool discipline) so a sim-time week
    # costs bounded disk and a console can query a downsampled window
    HISTORY_MAX_SLOTS: int = 512
    # growth-rate trending over the resource-footprint gauges: a
    # windowed least-squares fit per gauge; "growing" means projected
    # growth over one window exceeds max(FLOOR, FRACTION * mean level),
    # and only after SUSTAIN consecutive growing pool intervals does the
    # edge-triggered anomaly.alert.unbounded_growth page (one blip of a
    # breathing cache must not)
    HISTORY_GROWTH_WINDOW: float = 120.0
    HISTORY_GROWTH_MIN_POINTS: int = 8
    HISTORY_GROWTH_FLOOR: float = 64.0
    HISTORY_GROWTH_FRACTION: float = 0.5
    HISTORY_GROWTH_SUSTAIN: int = 3

    # --- elastic resharding (shards/reshard.py) ---
    # After the mapping epoch ratchets, the OLD owner keeps forwarding
    # stale-routed writes for the moved range to the new owner for this
    # long (the bounded dual-ownership handoff window); past it a
    # stale-epoch write is NACKed fail-closed (retryable after a map
    # refresh) instead of silently double-owned forever
    RESHARD_HANDOFF_WINDOW: float = 10.0
    # migrated txns replayed into the target sub-pool per service tick —
    # bounds how much of a prod cycle the copy cursor may consume
    RESHARD_COPY_BATCH: int = 64
    # the copy phase must reach the source tip and the target must order
    # the whole moved prefix within this budget or the migration ABORTS
    # (descriptors unchanged, source keeps ownership — fail closed)
    RESHARD_COPY_TIMEOUT: float = 120.0
    # after a migration finishes (DONE or ABORTED) the manager refuses
    # a new `maybe_split` for this long: a reshard must never chase its
    # own transient (the just-moved traffic skews the very imbalance
    # index that would trigger the next one)
    RESHARD_COOLDOWN: float = 30.0

    # --- autopilot control plane (control/autopilot.py) ---
    # False (the default) constructs NO autopilot at all: the fabric's
    # construction seam returns None and every loop pays one `is None`
    # check — today's behavior exactly, pinned by test
    AUTOPILOT: bool = False
    # decision cadence on the AGGREGATOR's fleet clock (seconds): the
    # autopilot only evaluates when snapshot arrivals have advanced
    # `aggregator.now` past the next mark, so decisions fire on
    # aggregator-interval arrivals and a recorded run replays exactly
    AUTOPILOT_INTERVAL: float = 1.0
    # how many CONSECUTIVE pool-interval judgments a signal must hold
    # before the autopilot acts on it (flap hysteresis, the breaker
    # pattern at fleet scale), and the longer bar an undo/recovery must
    # clear before an action is reverted
    AUTOPILOT_SUSTAIN: int = 3
    AUTOPILOT_RECOVER_SUSTAIN: int = 5
    # per-(policy, subject) cooldown stamped on every action: the same
    # policy may not touch the same subject again (including undoing
    # itself) until the stamp expires — no action/undo pair can fit
    # inside one cooldown window
    AUTOPILOT_COOLDOWN: float = 30.0
    # merges never shrink the fabric below this many shards
    AUTOPILOT_MIN_SHARDS: int = 2
    # a shard whose trailing ordered rate falls below mean * this factor
    # is the under-load merge candidate (only judged while NO shard is
    # hot, so under-load never fights a split)
    SHARD_UNDERLOAD_FACTOR: float = 0.25
    # degradation ladder: level 1 divides every front door's effective
    # shed watermark by this factor (shed harder), level 2 parks
    # ordering pool-wide (read-only) — entered only when burn persists
    # for 2x AUTOPILOT_SUSTAIN despite the reshard/lane/observer
    # policies, stepped back one level at a time on recovery
    AUTOPILOT_SHED_FACTOR: int = 4
    # observer fan-out bounds per region (policy 3)
    AUTOPILOT_OBSERVER_MIN: int = 1
    AUTOPILOT_OBSERVER_MAX: int = 4
    # Proof-CDN absorption bar (reads/edge.py): a region whose windowed
    # edge hit-rate is at or above this fraction has its read demand
    # absorbed by the keyless cache tier — the observer spawn policy
    # HOLDS (with the rate as ledger evidence) instead of adding
    # observer capacity the edges already make redundant
    AUTOPILOT_EDGE_ABSORB: float = 0.95

    # --- proof-carrying cross-shard writes (shards/cross_write.py) ---
    # participant lock TTL: a remote shard holding a lock with no
    # anchored decision from the coordinator resolves (verified read of
    # the decision record) after this long, and aborts fail-closed on a
    # proven absence. MUST comfortably exceed XSW_PREPARE_TTL: the
    # coordinator refuses to order a commit past its prepare deadline,
    # which is what makes the participant's absence-abort safe
    XSW_LOCK_TTL: float = 20.0
    # coordinator prepare TTL: past it the coordinator (or its shard's
    # recovery sweep) orders an ABORT decision and never a commit
    XSW_PREPARE_TTL: float = 8.0

    # --- blacklisting (TTL: self-isolation must heal; see blacklister.py) ---
    BLACKLIST_TTL: float = 120.0

    # --- propagation ---
    # digest-gossip: at most ONE node (digest-designated) broadcasts the
    # full request body; every other propagate is a ~100-byte digest vote,
    # with on-demand body fetch through MessageReq. False restores the
    # reference's full-body flooding (n*(n-1) body sends per txn) — kept
    # as a measurement/compat switch.
    DIGEST_GOSSIP: bool = True
    # grace before fetching a body we only hold digest votes for (the
    # client's own broadcast or the disseminator's body usually outruns
    # it), and the per-candidate retry cadence of the fetch loop
    PROPAGATE_BODY_FETCH_DELAY: float = 0.5
    PROPAGATE_BODY_FETCH_RETRY: float = 1.0
    # states holding only digest VOTES (no verified body) are swept on a
    # much shorter leash than the general unfinalized TTL: they cost a
    # transport-authenticated peer nothing to mint (~100 B, no client
    # signature behind them), so an hours-scale retention would hand one
    # faulty validator a memory-exhaustion lever. Long enough for any
    # honest fetch cycle (grace delay + a full voter rotation) to resolve.
    PROPAGATE_BODYLESS_REQ_TIMEOUT: float = 60.0
    # requests that never reach the propagate quorum are freed after this
    # (ref config.py PROPAGATES_PHASE_REQ_TIMEOUT)
    PROPAGATES_PHASE_REQ_TIMEOUT: float = 3600.0
    # executed request state is RETAINED this long so peers can still serve
    # MessageReq(PROPAGATE) for a request that already ordered — freeing at
    # execution would wedge any node that missed both the PROPAGATE and the
    # PRE-PREPARE until a checkpoint-lag catchup 100 batches later
    EXECUTED_REQ_RETENTION: float = 120.0

    # --- ingress plane (ingress/plane.py): the pool's front door ---
    # per-client bounded queue: one flooding client can hold at most this
    # many writes queued before ITS OWN new arrivals shed (other clients'
    # queues are untouched — fairness before the global watermark)
    INGRESS_CLIENT_QUEUE_CAP: int = 32
    # global watermarks over the SUM of all client queues: at the high
    # mark new arrivals shed (explicit LoadShed reply) until the total
    # drains below the low mark — hysteresis so the plane sheds decisively
    # instead of flapping at the boundary (shed-before-wedge)
    INGRESS_HIGH_WATERMARK: int = 4096
    INGRESS_LOW_WATERMARK: int = 1024
    # per-tick weighted-fair dequeue budget into the batched verifier; the
    # ingress controller steers the effective budget within [MIN, MAX]
    INGRESS_ADMIT_MAX: int = 512
    INGRESS_ADMIT_MIN: int = 64
    # how often the plane drains its queues into one auth batch
    INGRESS_TICK_INTERVAL: float = 0.02
    # AIMD admission controller (ingress/controller.py): steers the
    # dequeue budget and the effective shed watermark from queue-wait p95
    # toward the SLO below. False freezes both knobs at config values.
    INGRESS_CONTROLLER: bool = True
    INGRESS_SLO_P95: float = 0.25       # queue-wait p95 target (seconds)
    INGRESS_CONTROL_INTERVAL: float = 0.5

    # --- observer read fan-out (ingress/observer_reads.py) ---
    # an observer whose newest verified anchor is older than this serves
    # PROOFLESS (the client escalates to a validator) instead of shipping
    # a stale proof the client would reject anyway; defaults to the read
    # plane's client-side freshness bound
    OBSERVER_ANCHOR_LAG_MAX: float = 900.0

    # --- crypto backend seam: 'cpu' or 'jax' (the north star switch) ---
    crypto_backend: str = "cpu"

    # --- fused crypto pipeline (parallel/pipeline.py) ---
    # One submission ring coalescing Ed25519 client-auth, BLS batch
    # checks, and Merkle hashing across consensus stages AND co-hosted
    # nodes, with double-buffered device dispatch. A process whose
    # backend is a device it owns (`start_node --backend jax`,
    # `local_pool.build_pool(n, "jax")`) always builds it through
    # `make_crypto_pipeline`; the cpu and service backends never do —
    # the ring's coalescing pays for a device round trip, not for a
    # host loop.
    # pinned pad-bucket ladder (pow2 steps): every ed25519 wave pads to a
    # bucket in [MIN, MAX] so steady state never meets a novel XLA shape
    PIPELINE_MIN_BUCKET: int = 64
    PIPELINE_MAX_BUCKET: int = 4096
    # how long a partial wave is held for more submitters before it
    # auto-dispatches; the pipeline controller roams within [MIN, MAX]
    PIPELINE_FLUSH_WAIT: float = 0.005
    PIPELINE_FLUSH_WAIT_MIN: float = 0.001
    PIPELINE_FLUSH_WAIT_MAX: float = 0.05
    # closed-loop steering (PipelineController): decisions on sample
    # arrivals past this interval; False freezes both knobs at config
    PIPELINE_CONTROLLER: bool = True
    PIPELINE_CONTROL_INTERVAL: float = 0.5
    # submit->dispatch queue-wait p95 target the flush hold steers toward
    PIPELINE_SLO_P95: float = 0.05
    # unique SHA messages below this per flush stay on hashlib (a device
    # dispatch was once measured to cost more than ~1k host hashes; the
    # value is re-derived on the attached chip under ROADMAP C10)
    PIPELINE_SHA_MIN_BATCH: int = 1024
    # multi-device scale-out: shard the submission ring across this many
    # chips, one independently breakable lane per device (per-lane wave
    # queue + pinned-bucket set + breaker). 1 = the single-ring PR 8
    # pipeline exactly (no lane indirection — pinned by microbenchmark);
    # 0 = every local device. Lanes wrap when the host has fewer chips.
    PIPELINE_DEVICES: int = 1
    # per-lane dispatch threads: same-thread async dispatch SERIALIZES
    # executions across devices on the CPU backend (measured: 4 async
    # waves = 4x one wave; 4 threaded waves = 1x), so device-backed
    # lanes dispatch from a worker thread each. None = auto (threads
    # only for lanes pinned to a real device); False forces inline
    # dispatch (deterministic sims/fuzz).
    PIPELINE_LANE_THREADS: Optional[bool] = None
    # cross-host crypto federation (parallel/federation.py): comma-
    # separated crypto-service socket paths; each remote host appears as
    # one extra lane in the submission ring (its own wave queue, pinned
    # ladder negotiated over the wire, supervised breaker). "" (the
    # default) constructs the PR 14 single-host classes EXACTLY —
    # byte-identical behavior, pinned by microbenchmark.
    PIPELINE_REMOTE_HOSTS: str = ""
    # work-stealing between backlogged lanes: a lane whose staged
    # backlog exceeds the least-backlogged healthy lane's occupancy by
    # at least STEAL_THRESHOLD items donates half the delta; the
    # per-lane-pair COOLDOWN is the anti-flap hysteresis (a recent steal
    # in either direction blocks the reverse). A lane whose breaker is
    # open evacuates unconditionally — back to host-local lanes only.
    PIPELINE_STEAL_THRESHOLD: int = 32
    PIPELINE_STEAL_COOLDOWN: float = 0.25
    # fused commit wave (parallel/commit_wave.py): the ordered path
    # drains state-apply + triple-root recommit as level-synchronized
    # KIND_CMT dispatches whenever a pipeline is wired onto the
    # DatabaseManager. False keeps every root producer on its inline
    # host path (byte-identical roots either way — the flag is a
    # perf/debug switch, never a consensus-visible one).
    COMMIT_WAVE: bool = True

    # --- state commitment seam (state/commitment/) ---
    # scheme every ledger's state uses: 'mpt' (default; wire format
    # unchanged from the pre-interface code) or 'verkle' (wide-branching
    # KZG commitments with aggregated multi-key openings — one envelope
    # answers a whole client page; see docs/state_commitment.md)
    STATE_COMMITMENT: str = "mpt"
    # per-ledger overrides: {ledger_id: backend}; an entry wins over the
    # pool-wide default (e.g. verkle for the read-heavy domain ledger,
    # mpt for pool/config). Every node of a pool MUST agree — the
    # backend defines the signed root anchors
    STATE_COMMITMENT_PER_LEDGER: dict = field(default_factory=dict)
    # Verkle branching factor (power of two <= 256). 256 = one stem byte
    # per level, depth ~2 at 10k keys; smaller widths only for tests
    VERKLE_WIDTH: int = 256

    # --- storage ---
    kv_backend: str = "memory"          # 'memory' | 'file'

    # --- misc ---
    ACCEPTABLE_DEVIATION_PREPREPARE_SECS: float = 600.0
    OUTDATED_REQS_CHECK_INTERVAL: float = 60.0

    def replace(self, **overrides) -> "Config":
        return dataclasses.replace(self, **overrides)


def load_config(*override_layers: Optional[dict]) -> Config:
    """Defaults overlaid with dict layers (install < network < user), mirroring
    the reference's getConfig merge order."""
    merged: dict[str, Any] = {}
    for layer in override_layers:
        if layer:
            merged.update(layer)
    known = {f.name for f in dataclasses.fields(Config)}
    unknown = set(merged) - known
    if unknown:
        raise KeyError(f"unknown config keys: {sorted(unknown)}")
    return Config(**merged)
