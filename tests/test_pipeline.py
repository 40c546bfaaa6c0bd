"""Fused crypto pipeline (parallel/pipeline.py): recompile guard, fused
Merkle equivalence, ring dedup, double buffering, controller steering,
supervisor composition, and the disabled-overhead bound."""
import random
import time

import numpy as np
import pytest

from plenum_tpu.config import Config
from plenum_tpu.crypto.ed25519 import (CpuEd25519Verifier, Ed25519Signer,
                                       JaxEd25519Verifier)
from plenum_tpu.parallel.pipeline import (CryptoPipeline,
                                          PipelineController,
                                          make_crypto_pipeline)


class FakeDeviceVerifier(JaxEd25519Verifier):
    """Records dispatched batch shapes and answers instantly (verdict
    content is irrelevant to the shape/buffer tests). Subclassing the jax
    verifier makes the pipeline treat it as device-backed (bucket pad)."""

    def __init__(self):
        super().__init__(min_batch=1)
        self.shapes: list[int] = []

    def submit_batch(self, items):
        self.shapes.append(len(items))
        return np.ones(len(items), dtype=bool)

    def collect_batch(self, token, wait=True):
        return token


class ManualDeviceVerifier(FakeDeviceVerifier):
    """Like FakeDeviceVerifier, but resolution is handed out manually —
    the double-buffer test controls exactly when a wave 'lands'."""

    def __init__(self):
        super().__init__()
        self.pending: list[dict] = []

    def submit_batch(self, items):
        self.shapes.append(len(items))
        tok = {"n": len(items), "ready": False}
        self.pending.append(tok)
        return tok

    def collect_batch(self, token, wait=True):
        if not token["ready"] and not wait:
            return None
        token["ready"] = True
        return np.ones(token["n"], dtype=bool)


def _junk_items(rng, n):
    """Unique well-FORMED triples (content correctness is not under test
    — the fake inner answers all-True). The S half's top byte is zeroed
    so S < L: the ring settles malformed/malleable lanes as False
    without dispatching them, and these must reach the device."""
    return [(rng.randbytes(20), rng.randbytes(63) + b"\x00",
             rng.randbytes(32)) for _ in range(n)]


def _fast_config(**over):
    return Config(PIPELINE_MIN_BUCKET=16, PIPELINE_MAX_BUCKET=64,
                  PIPELINE_FLUSH_WAIT=0.0, **over)


def test_recompile_guard_flat_across_mixed_waves():
    """Steady-state compile count stays FLAT across 100 mixed-size waves:
    after one warmup wave per pinned bucket shape, no novel shape may
    ever be dispatched (a recompile costs minutes per shape)."""
    rng = random.Random(11)
    inner = FakeDeviceVerifier()
    pipe = CryptoPipeline(ed_inner=inner, config=_fast_config())

    # warmup: one wave per bucket in the pinned ladder (16, 32, 64)
    for size in (3, 20, 40):
        tok = pipe.submit_verify(_junk_items(rng, size))
        pipe.flush()
        assert pipe.collect_verify(tok) is not None
    warm_shapes = pipe.compiled_shapes
    pipe.pin()

    for _ in range(100):
        tok = pipe.submit_verify(_junk_items(rng, rng.randint(1, 60)))
        pipe.flush()
        assert pipe.collect_verify(tok) is not None
    assert pipe.compiled_shapes == warm_shapes, \
        "steady state met a novel dispatch shape"
    assert pipe.stats["unpinned_shapes"] == 0
    # every dispatched batch landed exactly on a pinned bucket
    assert set(inner.shapes) <= {16, 32, 64}


def test_pinned_enforcement_pads_and_splits_to_compiled_shapes():
    """After pin(), a wave size with NO compiled bucket must not compile
    one: it pads up to the smallest compiled bucket that fits or splits
    at the largest — a novel mid-run shape costs a 25-45 s XLA
    retrace+compile (the measured 206 -> 5.7 TPS collapse), padding
    costs microseconds."""
    rng = random.Random(19)
    inner = FakeDeviceVerifier()
    pipe = CryptoPipeline(ed_inner=inner, config=_fast_config())
    # warm ONLY bucket 16 (the single-txn warmup shape), then pin
    tok = pipe.submit_verify(_junk_items(rng, 3))
    pipe.flush()
    assert pipe.collect_verify(tok) is not None
    assert pipe.compiled_shapes == 1
    pipe.pin()
    # 40 items would naturally pick bucket 64 — enforcement must split
    # into 16-lane waves instead (the only compiled shape)
    tok = pipe.submit_verify(_junk_items(rng, 40))
    out = pipe.collect_verify(tok, wait=True)
    assert out is not None and len(out) == 40 and out.all()
    assert set(inner.shapes) == {16}
    assert pipe.compiled_shapes == 1
    assert pipe.stats["unpinned_shapes"] == 0


def test_prewarm_compiles_ladder_then_steady_state_never_recompiles():
    rng = random.Random(29)
    inner = FakeDeviceVerifier()
    pipe = CryptoPipeline(ed_inner=inner, config=_fast_config())
    assert pipe.prewarm([16, 32]) == [16, 32]
    assert set(inner.shapes) == {16, 32}
    assert pipe.compiled_shapes == 2
    pipe.pin()
    # prewarm lanes (all-zero verkey) must not poison the verdict cache
    assert not pipe._ed_cache
    for size in (1, 10, 17, 30, 60):
        tok = pipe.submit_verify(_junk_items(rng, size))
        out = pipe.collect_verify(tok, wait=True)
        assert out is not None and len(out) == size
    assert set(inner.shapes) == {16, 32}       # 60 split as 32+{16,32}
    assert pipe.stats["unpinned_shapes"] == 0
    # a cpu-backed (unbucketed) pipeline has no shapes to compile
    assert CryptoPipeline(ed_inner=CpuEd25519Verifier(),
                          config=_fast_config()).prewarm([16]) == []


def test_bucket_padding_and_overflow_split():
    rng = random.Random(5)
    pipe = CryptoPipeline(ed_inner=FakeDeviceVerifier(),
                          config=_fast_config())
    # 150 items > max bucket 64: the wave splits, leftovers ride the next
    tok = pipe.submit_verify(_junk_items(rng, 150))
    out = pipe.collect_verify(tok, wait=True)
    assert out is not None and len(out) == 150 and out.all()
    assert pipe.stats["overflow_waves"] >= 1
    assert pipe.stats["dispatches"] >= 3          # 64 + 64 + 22


def test_malformed_lanes_settle_before_dispatch():
    """Malformed/malleable items (short sig, wrong-size vk, S >= L) are
    settled False in the ring and never ride a wave: the dispatched
    batch length always equals the padded bucket. The device verifier's
    own staging screen drops such lanes AFTER the ring pads, so letting
    them through would shrink the real device shape under the one the
    guard recorded and pin() enforced — a novel mid-run compile."""
    rng = random.Random(31)
    inner = FakeDeviceVerifier()
    pipe = CryptoPipeline(ed_inner=inner, config=_fast_config())
    good = _junk_items(rng, 10)
    bad = [
        (b"m", b"\x01" * 63, b"\x02" * 32),          # short sig
        (b"m", b"\x01" * 64, b"\x02" * 31),          # short vk
        (b"m", b"\xff" * 64, b"\x02" * 32),          # S >= L (malleable)
        (b"m", None, b"\x02" * 32),                  # not bytes at all
    ]
    tok = pipe.submit_verify(good + bad)
    out = pipe.collect_verify(tok, wait=True)
    assert list(out) == [True] * 10 + [False] * 4
    assert inner.shapes == [16], \
        "screened lanes changed the dispatched device shape"


def test_ring_dedup_across_submitters():
    """Co-hosted nodes stage IDENTICAL items; the ring dispatches each
    unique triple once and publishes the dedup ratio."""
    rng = random.Random(7)
    inner = FakeDeviceVerifier()
    pipe = CryptoPipeline(ed_inner=inner, config=_fast_config())
    items = _junk_items(rng, 10)
    v1, v2, v3 = pipe.verifier(), pipe.verifier(), pipe.verifier()
    toks = [v.submit_batch(items) for v in (v1, v2, v3)]
    pipe.flush()
    for v, tok in zip((v1, v2, v3), toks):
        got = v.collect_batch(tok, wait=True)
        assert got is not None and len(got) == 10
    assert pipe.stats["dispatched_items"] == 10      # once, not 30
    assert pipe.stats["dedup_hits"] == 20
    assert pipe.dedup_ratio() == pytest.approx(20 / 30)
    # a later identical batch rides the verdict cache: no new dispatch
    before = pipe.stats["dispatches"]
    assert v1.verify_batch(items) is not None
    assert pipe.stats["dispatches"] == before


class ContentVerdictVerifier(FakeDeviceVerifier):
    """A lane's verdict is its message's first bit, so a verdict that
    lands in another submitter's span shows."""

    def submit_batch(self, items):
        self.shapes.append(len(items))
        return np.array([bool(it[0][0] & 1) for it in items], dtype=bool)


def test_ring_merges_submitters_batches_into_one_dispatch():
    """Batches that several submitters stage in one cycle, all of them
    distinct, ride ONE device dispatch, and each submitter gets back the
    verdicts of its own items in its own order."""
    rng = random.Random(13)
    inner = ContentVerdictVerifier()
    # a hold no test outlasts: only the pool's flush() cuts the wave
    pipe = CryptoPipeline(ed_inner=inner, config=Config(
        PIPELINE_MIN_BUCKET=16, PIPELINE_MAX_BUCKET=64,
        PIPELINE_FLUSH_WAIT=60.0, PIPELINE_FLUSH_WAIT_MAX=60.0))
    views = [pipe.verifier() for _ in range(3)]
    toks, expects = [], []
    for k, view in enumerate(views):          # 2, 3 and 4 items
        expect = [(i + k) % 3 != 0 for i in range(2 + k)]
        items = [(bytes([2 * rng.randrange(100) + good]) + rng.randbytes(8),
                  rng.randbytes(63) + b"\x00", rng.randbytes(32))
                 for good in expect]
        toks.append(view.submit_batch(items))
        expects.append(expect)
    assert inner.shapes == [], "dispatched before every submitter staged"
    pipe.flush()
    assert inner.shapes == [16]               # 9 items, one padded wave
    for view, tok, expect in zip(views, toks, expects):
        assert list(view.collect_batch(tok, wait=True)) == expect
    assert pipe.stats["dispatches"] == 1
    assert pipe.stats["dispatched_items"] == 9
    assert pipe.stats["dedup_hits"] == 0


def test_real_jax_wave_verdicts():
    """One real device wave end to end (JAX-on-CPU): good and bad
    signatures come back with the right verdicts through bucket padding
    and the wave cache."""
    signer = Ed25519Signer(seed=b"pipeline-wave-test".ljust(32, b"\0"))
    msgs = [b"wave-%d" % i for i in range(5)]
    items = [(m, signer.sign(m), signer.verkey) for m in msgs]
    items.append((b"forged", signer.sign(msgs[0]), signer.verkey))
    pipe = CryptoPipeline(
        ed_inner=JaxEd25519Verifier(min_batch=1),
        config=Config(PIPELINE_MIN_BUCKET=8, PIPELINE_MAX_BUCKET=8,
                      PIPELINE_FLUSH_WAIT=0.0))
    got = pipe.verifier().verify_batch(items)
    assert list(got) == [True] * 5 + [False]
    assert pipe.stats["dispatches"] == 1
    # cross-check vs the cpu backend on identical content
    assert list(CpuEd25519Verifier().verify_batch(items)) == list(got)


@pytest.mark.parametrize("supervised", [False, True])
def test_ring_counts_its_dispatches_by_the_program_that_ran(monkeypatch,
                                                            supervised):
    """`summary()` (VALIDATOR_INFO `plane.ring` where a node owns its
    chip) carries `dispatches_by_lanes`, counted where `dispatches` is,
    from the lane count of the program behind each wave's token. The
    ring pads to its own pinned ladder, so the verifier's rule (pad to
    the smallest held program) finds each wave at a held length."""
    import jax.numpy as jnp
    from plenum_tpu.ops import aot
    from plenum_tpu.parallel.supervisor import supervise
    ran = []

    def program(jitted, avals, device=None, wait=True):
        shape = (avals[0].shape[0], avals[2].shape[0])

        def run(s, h, keys, idx, r):
            ran.append((s.shape[0], keys.shape[0]))
            return jnp.ones(shape[0], dtype=bool)
        return run
    monkeypatch.setattr(aot, "has_entry", lambda *a, **k: True)
    monkeypatch.setattr(aot, "obtain", program)
    device = JaxEd25519Verifier(min_batch=1)
    pipe = CryptoPipeline(
        ed_inner=supervise(device) if supervised else device,
        config=_fast_config())
    assert pipe.prewarm([16, 32]) == [16, 32]
    pipe.pin()
    assert sorted(device._preloaded) == [(16, 16), (32, 32)]
    assert pipe.summary()["dispatches_by_lanes"] == {}  # warm-up is not
    rng = random.Random(5)                              # a ring dispatch
    for size in (3, 20, 9, 16):
        tok = pipe.submit_verify(_junk_items(rng, size))
        pipe.flush()
        assert pipe.collect_verify(tok) is not None
    summary = pipe.summary()
    assert summary["dispatches_by_lanes"] == {"16": 3, "32": 1}
    assert summary["dispatches"] == 4 and summary["unpinned_shapes"] == 0
    assert ran[2:] == [(16, 16), (32, 32), (16, 16), (16, 16)]


def test_a_ring_over_a_host_double_counts_no_lanes():
    rng = random.Random(6)
    pipe = CryptoPipeline(ed_inner=FakeDeviceVerifier(),
                          config=_fast_config())
    tok = pipe.submit_verify(_junk_items(rng, 5))
    pipe.flush()
    assert pipe.collect_verify(tok) is not None
    assert pipe.summary()["dispatches"] == 1
    assert pipe.summary()["dispatches_by_lanes"] == {}


def test_double_buffer_packs_while_inflight():
    """Host packs wave N+1 while the device runs wave N; the packed wave
    dispatches the moment N resolves — without any new flush call."""
    rng = random.Random(3)
    inner = ManualDeviceVerifier()
    pipe = CryptoPipeline(ed_inner=inner, config=_fast_config())
    t1 = pipe.submit_verify(_junk_items(rng, 20))
    pipe.flush()                                   # dispatch wave 1
    assert len(inner.pending) == 1
    t2 = pipe.submit_verify(_junk_items(rng, 20))
    pipe.service(force=True)                       # packs wave 2 only
    assert len(inner.pending) == 1, "dispatched while device busy"
    assert pipe._ed_packed is not None, "wave 2 not packed during flight"
    inner.pending[0]["ready"] = True               # wave 1 lands
    pipe.service()
    assert len(inner.pending) == 2, "packed wave did not auto-dispatch"
    inner.pending[1]["ready"] = True
    assert pipe.collect_verify(t1) is not None
    assert pipe.collect_verify(t2) is not None


def test_controller_steering_replay_identical():
    """Bucket floor grows on overflow, shrinks on chronic pad waste;
    flush wait shrinks when queue wait breaks the SLO. Decisions are a
    pure function of clock-stamped samples — two identical runs decide
    identically."""

    def run():
        clock = {"t": 0.0}
        cfg = Config(PIPELINE_MIN_BUCKET=16, PIPELINE_MAX_BUCKET=256,
                     PIPELINE_CONTROL_INTERVAL=1.0, PIPELINE_SLO_P95=0.05)
        ctl = PipelineController(cfg, lambda: clock["t"])
        log = []
        # phase 1: overflowing waves -> floor must grow
        for _ in range(8):
            clock["t"] += 0.3
            ctl.note_wave(0.001, 256, 256, overflowed=True)
            log.append((ctl.bucket_floor, round(ctl.flush_wait, 6)))
        grown = ctl.bucket_floor
        # phase 2: tiny fills -> floor decays back
        for _ in range(12):
            clock["t"] += 0.3
            ctl.note_wave(0.001, 2, grown, overflowed=False)
            log.append((ctl.bucket_floor, round(ctl.flush_wait, 6)))
        shrunk = ctl.bucket_floor
        # phase 3: queue waits past the SLO -> flush wait halves
        for _ in range(8):
            clock["t"] += 0.3
            ctl.note_wave(0.2, 12, 16, overflowed=False)
            log.append((ctl.bucket_floor, round(ctl.flush_wait, 6)))
        return grown, shrunk, ctl.flush_wait, log, ctl.decisions

    g1, s1, w1, log1, d1 = run()
    g2, s2, w2, log2, d2 = run()
    assert g1 > 16, "overflow did not grow the bucket floor"
    assert s1 < g1, "pad waste did not shrink the floor"
    assert w1 < Config().PIPELINE_FLUSH_WAIT, \
        "SLO-breaking queue wait did not shrink the flush hold"
    assert (g1, s1, w1, log1, d1) == (g2, s2, w2, log2, d2), \
        "controller decisions are not replay-identical"


def test_bls_lane_ring_dedup():
    """Identical BLS triples staged by co-hosted submitters settle on ONE
    inner batch_verify over the deduped union."""
    calls = []

    class FakeBls:
        def batch_verify(self, items):
            calls.append(list(items))
            return [True] * len(items)

    pipe = CryptoPipeline(ed_inner=FakeDeviceVerifier(),
                          bls_inner=FakeBls(), config=_fast_config())
    items = [("sig%d" % i, b"msg", "vk%d" % i) for i in range(6)]
    t1 = pipe.submit_bls(items)
    t2 = pipe.submit_bls(items)           # the co-hosted twin
    assert pipe.collect_bls(t1) == [True] * 6
    assert pipe.collect_bls(t2) == [True] * 6
    assert len(calls) == 1 and len(calls[0]) == 6
    assert pipe.stats["bls_unique"] == 6
    assert pipe.stats["dedup_hits"] >= 6


def test_sha_lane_and_tree_hasher_dedup():
    """The pipelined tree hasher's digests match hashlib exactly, and two
    replicas hashing the SAME leaf wave pay the work once."""
    from plenum_tpu.ledger.compact_merkle_tree import CompactMerkleTree
    from plenum_tpu.ledger.tree_hasher import TreeHasher

    pipe = CryptoPipeline(ed_inner=FakeDeviceVerifier(),
                          config=_fast_config())
    h1, h2 = pipe.tree_hasher(), pipe.tree_hasher()
    ref = TreeHasher()
    leaves = [b"txn-%d" % i for i in range(40)]
    assert h1.hash_leaves(leaves) == ref.hash_leaves(leaves)
    pairs = list(zip(ref.hash_leaves(leaves[0::2]),
                     ref.hash_leaves(leaves[1::2])))
    assert h1.hash_children_batch(pairs) == ref.hash_children_batch(pairs)
    before_unique = pipe.stats["sha_unique"]
    assert h2.hash_leaves(leaves) == ref.hash_leaves(leaves)
    assert pipe.stats["sha_unique"] == before_unique, \
        "replica twin re-hashed cached leaves"
    # whole trees through the pipelined hasher agree with pure python
    t_ref = CompactMerkleTree(TreeHasher())
    t_pipe = CompactMerkleTree(pipe.tree_hasher())
    rng = random.Random(23)
    for _ in range(10):
        chunk = [rng.randbytes(rng.randint(1, 40))
                 for _ in range(rng.randint(1, 30))]
        t_ref.extend_batch(chunk)
        t_pipe.extend_batch(chunk)
        assert t_ref.root_hash == t_pipe.root_hash


def test_fused_merkle_root_equivalence_random():
    """Fused-wave appends (one device program for all wide interior
    levels) produce byte-identical roots and proofs vs the pure-Python
    hasher across random leaf sets and arbitrary base alignments."""
    from plenum_tpu.ledger.compact_merkle_tree import CompactMerkleTree
    from plenum_tpu.ledger.tree_hasher import JaxTreeHasher, TreeHasher

    rng = random.Random(41)
    ref = CompactMerkleTree(TreeHasher())
    # min_batch huge: leaf hashing stays on hashlib; ONLY the fused
    # interior path is under test (fuse_min=2 forces it for every wave)
    fused = CompactMerkleTree(JaxTreeHasher(min_batch=10**9, fuse_min=2))
    total = 0
    for step in range(25):
        chunk = [rng.randbytes(rng.randint(1, 60))
                 for _ in range(rng.randint(1, 40))]
        ref.extend_batch(chunk)
        fused.extend_batch(chunk)
        total += len(chunk)
        assert ref.root_hash == fused.root_hash, f"root diverged @{step}"
        assert ref.tree_size == fused.tree_size
    for m in (0, 1, total // 3, total - 1):
        assert ref.inclusion_proof(m) == fused.inclusion_proof(m)
    for m in (1, 2, total // 2, total):
        assert ref.consistency_proof(m) == fused.consistency_proof(m)


def test_supervisor_composition_wedge_falls_back():
    """The pipeline dispatches THROUGH the supervised verifier: a wedged
    device degrades a wave to hedged CPU verdicts (correct, bounded) and
    the breaker records the failure — device_flap composes unchanged."""
    from plenum_tpu.parallel.faults import FaultyVerifier
    from plenum_tpu.parallel.supervisor import (CircuitBreaker,
                                                DeadlineBudget,
                                                SupervisedVerifier)

    faulty = FaultyVerifier(CpuEd25519Verifier())
    sup = SupervisedVerifier(
        faulty, fallback=CpuEd25519Verifier(),
        breaker=CircuitBreaker(fail_threshold=1, cooldown=60.0),
        budget=DeadlineBudget(base=0.2, min_s=0.1, warm_max=0.3,
                              cold_max=0.3))
    pipe = CryptoPipeline(ed_inner=sup, config=_fast_config())
    signer = Ed25519Signer(seed=b"pipe-flap".ljust(32, b"\0"))
    items = [(b"m%d" % i, signer.sign(b"m%d" % i), signer.verkey)
             for i in range(3)]
    faulty.wedge()
    got = pipe.verifier().verify_batch(items)
    assert list(got) == [True, True, True]
    assert sup.stats["hedge_wins"] + sup.stats["fallback_batches"] >= 1
    assert sup.stats["verdict_forks"] == 0
    # breaker open: the next wave routes straight to CPU, unpadded
    fresh = [(b"x%d" % i, signer.sign(b"x%d" % i), signer.verkey)
             for i in range(3)]
    got2 = pipe.verifier().verify_batch(fresh)
    assert list(got2) == [True, True, True]
    assert sup.stats["open_circuit_fallbacks"] >= 1


def test_disabled_pipeline_overhead_bound():
    """A backend that owns no device (cpu, service) returns None from
    the construction seam, and the per-prod-cycle cost without a ring —
    the `pipeline is not None` gate — stays NullTracer-grade: under 2%
    of a 1 ms/txn budget across 1000 checks."""
    assert make_crypto_pipeline(Config(), "cpu") is None
    assert make_crypto_pipeline(Config(), "service") is None
    from plenum_tpu.node.bootstrap import NodeBootstrap
    comp = NodeBootstrap("OverheadNode").build()
    assert comp.pipeline is None
    assert not type(comp.authenticator.core_authenticator.verifier
                    ).__name__.startswith("Pipeline")
    n = 1000
    t0 = time.perf_counter()
    hits = 0
    for _ in range(n):
        if comp.pipeline is not None:     # the exact prod-loop gate
            hits += 1
    per_check = (time.perf_counter() - t0) / n
    assert hits == 0
    assert per_check < 0.02e-3, \
        f"disabled gate costs {per_check * 1e6:.2f}us per prod cycle"


def test_make_crypto_pipeline_constructs_for_device_backends():
    pipe = make_crypto_pipeline(Config(), "jax")
    assert type(pipe) is CryptoPipeline
    from plenum_tpu.parallel.supervisor import find_supervisor
    assert find_supervisor(pipe.verifier()) is not None, \
        "pipeline verifier chain hides the supervisor from node wiring"


# --- multi-device lanes (ISSUE 14 tentpole) --------------------------------

def _multi_pipe(n_lanes=4, **over):
    from plenum_tpu.parallel.pipeline import MultiDeviceCryptoPipeline
    inners = [FakeDeviceVerifier() for _ in range(n_lanes)]
    pipe = MultiDeviceCryptoPipeline(ed_inners=inners,
                                     config=_fast_config(**over),
                                     threaded=False)
    return pipe, inners


def test_multidevice_placement_and_dispatch_spread():
    """Co-hosted shard tags pin to distinct chips (tag % lanes); the
    unhinted path spreads waves over every healthy lane; per-lane
    dispatch counts and device_state tell the story."""
    rng = random.Random(53)
    pipe, inners = _multi_pipe(4)
    assert [pipe.place(t) for t in range(6)] == [0, 1, 2, 3, 0, 1]
    # hinted: lane 2 gets the wave, nobody else
    tok = pipe.submit_verify(_junk_items(rng, 8), lane=2)
    out = pipe.collect_verify(tok, wait=True)
    assert out is not None and len(out) == 8
    assert pipe.lanes[2].stats["dispatches"] == 1
    assert all(pipe.lanes[k].stats["dispatches"] == 0 for k in (0, 1, 3))
    # unhinted: waves spread across all lanes
    toks = [pipe.submit_verify(_junk_items(rng, 8)) for _ in range(8)]
    for t in toks:
        assert pipe.collect_verify(t, wait=True) is not None
    spread = [l.stats["dispatches"] for l in pipe.lanes]
    assert all(d >= 1 for d in spread), spread
    state = pipe.device_state()
    assert [d["lane"] for d in state] == [0, 1, 2, 3]
    assert sum(d["dispatches"] for d in state) == sum(spread)
    assert pipe.summary()["lanes"] == 4


def test_multidevice_per_lane_prewarm_pin_enforcement():
    """prewarm compiles each chip's OWN ladder; after pin() every lane
    enforces ITS compiled shapes (pad up / split), so steady state never
    recompiles on ANY chip — the per-lane twin of the PR 8 guard."""
    rng = random.Random(59)
    pipe, inners = _multi_pipe(3)
    assert pipe.prewarm([16, 32]) == [16, 32]
    for inner in inners:
        assert set(inner.shapes) == {16, 32}
    warm = pipe.compiled_shapes
    pipe.pin()
    for _ in range(60):
        tok = pipe.submit_verify(_junk_items(rng, rng.randint(1, 60)),
                                 lane=rng.randint(0, 5))
        assert pipe.collect_verify(tok, wait=True) is not None
    assert pipe.compiled_shapes == warm, \
        "steady state met a novel dispatch shape on some lane"
    assert pipe.stats["unpinned_shapes"] == 0
    for inner in inners:
        assert set(inner.shapes) <= {16, 32}


def test_multidevice_one_lane_breaker_isolation():
    """A wedged chip opens THAT lane's breaker only: its pinned waves
    degrade to host fallback, the other lanes keep dispatching to their
    devices, and unhinted traffic routes around the sick chip."""
    from plenum_tpu.parallel.faults import FaultyVerifier
    from plenum_tpu.parallel.pipeline import MultiDeviceCryptoPipeline
    from plenum_tpu.parallel.supervisor import (CLOSED, CircuitBreaker,
                                                DeadlineBudget,
                                                SupervisedVerifier)
    faulties, sups = [], []
    for k in range(3):
        f = FaultyVerifier(CpuEd25519Verifier(), device_index=k)
        s = SupervisedVerifier(
            f, fallback=CpuEd25519Verifier(),
            breaker=CircuitBreaker(fail_threshold=1, cooldown=60.0),
            budget=DeadlineBudget(base=0.2, min_s=0.1, warm_max=0.3,
                                  cold_max=0.3),
            label=f"lane{k}")
        faulties.append(f)
        sups.append(s)
    pipe = MultiDeviceCryptoPipeline(ed_inners=sups,
                                     config=_fast_config(),
                                     threaded=False)
    signer = Ed25519Signer(seed=b"lane-iso".ljust(32, b"\0"))
    mk = lambda tag, n=3: [
        (b"%s-%d" % (tag, i), signer.sign(b"%s-%d" % (tag, i)),
         signer.verkey) for i in range(n)]
    faulties[1].wedge()
    got = pipe.collect_verify(pipe.submit_verify(mk(b"w"), lane=1),
                              wait=True)
    assert list(got) == [True] * 3          # host fallback, correct
    assert sups[1].breaker.state != CLOSED
    assert sups[0].breaker.state == CLOSED
    assert sups[2].breaker.state == CLOSED
    # unhinted traffic avoids the open lane entirely
    for i in range(4):
        pipe.collect_verify(pipe.submit_verify(mk(b"u%d" % i)), wait=True)
    assert pipe.lanes[1].stats["dispatches"] == 1   # only its pinned wave
    assert (pipe.lanes[0].stats["dispatches"]
            + pipe.lanes[2].stats["dispatches"]) >= 4
    # telemetry story: device_state names the sick chip
    state = {d["lane"]: d["breaker"] for d in pipe.device_state()}
    assert state[1] == "open" and state[0] == "closed"


def test_multidevice_threaded_lanes_concurrent_dispatch():
    """Threaded lanes (the device-pinned production shape) resolve waves
    from worker threads: N in-flight waves make progress without the
    pump blocking on any one of them."""
    import threading

    class SlowDev(FakeDeviceVerifier):
        def __init__(self):
            super().__init__()
            self.gate = threading.Event()

        def submit_batch(self, items):
            self.shapes.append(len(items))
            return np.ones(len(items), dtype=bool)

        def collect_batch(self, token, wait=True):
            self.gate.wait(timeout=5.0)
            return token

    from plenum_tpu.parallel.pipeline import MultiDeviceCryptoPipeline
    rng = random.Random(61)
    inners = [SlowDev() for _ in range(3)]
    pipe = MultiDeviceCryptoPipeline(ed_inners=inners,
                                     config=_fast_config(),
                                     threaded=True)
    toks = [pipe.submit_verify(_junk_items(rng, 8), lane=k)
            for k in range(3)]
    pipe.service(force=True)
    assert all(l.inflight is not None for l in pipe.lanes), \
        "three waves must fly CONCURRENTLY, one per lane"
    for inner in inners:
        inner.gate.set()
    for t in toks:
        out = pipe.collect_verify(t, wait=True)
        assert out is not None and len(out) == 8
    pipe.close()


def test_single_device_path_is_pr8_pipeline_exactly():
    """n_devices == 1 pools pay NO sharding overhead: the construction
    seam returns the PR 8 CryptoPipeline CLASS itself (no lane
    indirection on the hot path), and per-op submit/collect cost stays
    within noise of a directly-built PR 8 ring."""
    from plenum_tpu.parallel.pipeline import MultiDeviceCryptoPipeline
    p1 = make_crypto_pipeline(Config(PIPELINE_DEVICES=1), "jax")
    assert type(p1) is CryptoPipeline, \
        "single-device pool got the multi-device class"
    assert not isinstance(p1, MultiDeviceCryptoPipeline)
    # default config IS the single-device config
    assert Config().PIPELINE_DEVICES == 1

    def drive(pipe, n_ops=60):
        rng = random.Random(67)
        t0 = time.perf_counter()
        for _ in range(n_ops):
            tok = pipe.submit_verify(_junk_items(rng, 8))
            pipe.collect_verify(tok, wait=True)
        return (time.perf_counter() - t0) / n_ops

    baseline = CryptoPipeline(ed_inner=FakeDeviceVerifier(),
                              config=_fast_config())
    seamed = make_crypto_pipeline(
        Config(PIPELINE_MIN_BUCKET=16, PIPELINE_MAX_BUCKET=64,
               PIPELINE_FLUSH_WAIT=0.0, PIPELINE_DEVICES=1),
        "jax", ed_inner=FakeDeviceVerifier())
    drive(baseline, 10)                     # warm both paths
    drive(seamed, 10)
    per_base = drive(baseline)
    per_seam = drive(seamed)
    # same class, same code: anything past 3x is a real regression, not
    # host noise (the loop is pure-python ring work, ~tens of us/op)
    assert per_seam < per_base * 3 + 1e-3, \
        f"single-device seam {per_seam * 1e6:.0f}us/op vs PR 8 " \
        f"{per_base * 1e6:.0f}us/op"


# --- commit-wave (cmt) lane pin ladder ---------------------------------------

class RecordingCmtEngine:
    """Device-style commitment engine fake: records dispatched wave
    sizes (the compiled-shape story is all these tests care about) and
    answers each job with a distinct marker — commitment semantics are
    covered by the state-commitment suite."""

    def __init__(self):
        self.shapes: list[int] = []

    def run_jobs(self, jobs):
        self.shapes.append(len(jobs))
        return [("res", job) for job in jobs]


def _cmt_jobs(tag, n):
    """n unique well-formed commit jobs (content irrelevant: the fake
    engine answers markers; uniqueness defeats the ring's dedup)."""
    return [("commit", 16, ((i, tag * 1000 + i),)) for i in range(n)]


def test_prewarm_cmt_compiles_ladder_and_rejects_non_pow2():
    """prewarm_cmt runs one all-pad wave per bucket through the engine
    (a lane that cannot compile must fail loudly in warmup, never
    degrade silently under load) and notes the shapes onto the cmt pin
    ladder; non-pow2 buckets are rejected before touching the device."""
    eng = RecordingCmtEngine()
    pipe = CryptoPipeline(cmt_inner=eng, config=_fast_config())
    assert pipe.prewarm_cmt([8, 4]) == [4, 8]
    assert eng.shapes == [4, 8]
    assert pipe._cmt_buckets() == [4, 8]
    with pytest.raises(ValueError):
        pipe.prewarm_cmt([6])
    # a short prewarm wave is a loud failure, not a silent degrade
    class Short:
        def run_jobs(self, jobs):
            return []
    with pytest.raises(RuntimeError):
        CryptoPipeline(cmt_inner=Short(),
                       config=_fast_config()).prewarm_cmt([4])
    # engine-less (host) pipelines still note the enforcement ladder
    host = CryptoPipeline(config=_fast_config())
    assert host.prewarm_cmt([16]) == [16]
    assert host._cmt_buckets() == [16]


def test_pinned_cmt_novel_shape_pads_and_splits_not_recompiles():
    """The cmt twin of the ed pin guard: after prewarm_cmt + pin(), a
    novel mid-run cmt wave size pads up to the smallest compiled bucket
    that fits or splits at the largest — never a fresh compile (the
    same XLA retrace a novel ed shape costs on a device MSM engine)."""
    eng = RecordingCmtEngine()
    pipe = CryptoPipeline(cmt_inner=eng, config=_fast_config())
    assert pipe.prewarm_cmt([4, 8]) == [4, 8]
    warm = pipe.compiled_shapes
    pipe.pin()
    eng.shapes.clear()
    # 5 unique jobs: pads up to bucket 8 (smallest compiled that fits)
    jobs = _cmt_jobs(1, 5)
    out = pipe.collect_commitment(pipe.submit_commitment(jobs))
    assert out == [("res", j) for j in jobs]
    assert eng.shapes == [8]
    # 21 unique jobs: split 8 + 8 at the ladder cap, tail padded to 8
    jobs = _cmt_jobs(2, 21)
    out = pipe.collect_commitment(pipe.submit_commitment(jobs))
    assert out == [("res", j) for j in jobs]
    assert set(eng.shapes) == {8}
    assert pipe.compiled_shapes == warm, \
        "steady state met a novel cmt dispatch shape"
    assert pipe.stats["unpinned_shapes"] == 0


def test_cmt_hlev_levels_bypass_engine_but_ride_the_fused_flush():
    """"hlev" hashing levels never reach the MSM engine (no engine
    implements them): a mixed flush dispatches the commit jobs to the
    engine at a pinned bucket while the hash level resolves in the same
    wave — and the flush still lands on zero unpinned shapes."""
    import hashlib
    eng = RecordingCmtEngine()
    pipe = CryptoPipeline(cmt_inner=eng, config=_fast_config())
    pipe.prewarm_cmt([4])
    pipe.pin()
    eng.shapes.clear()
    lev = ("hlev", "sha3", (b"node-a", b"node-b"))
    jobs = _cmt_jobs(3, 2) + [lev]
    out = pipe.collect_commitment(pipe.submit_commitment(jobs))
    assert out[:2] == [("res", j) for j in jobs[:2]]
    assert out[2] == tuple(hashlib.sha3_256(m).digest()
                           for m in (b"node-a", b"node-b"))
    assert eng.shapes == [4]          # 2 commit jobs padded to bucket 4
    assert pipe.stats["unpinned_shapes"] == 0


# --- cross-host federation (parallel/federation.py) --------------------------

def _fed_pipe(n_local=2, n_remote=1, **over):
    from plenum_tpu.parallel.federation import FederatedCryptoPipeline
    locals_ = [FakeDeviceVerifier() for _ in range(n_local)]
    remotes = [FakeDeviceVerifier() for _ in range(n_remote)]
    pipe = FederatedCryptoPipeline(
        ed_inners=locals_, remote_inners=remotes,
        hosts=[f"/tmp/fake{j}.sock" for j in range(n_remote)],
        config=_fast_config(**over), threaded=False)
    return pipe, locals_, remotes


def test_federated_stolen_items_never_double_verified():
    """Work-stealing moves whole, fully-unplanned tokens, so each
    distinct item reaches exactly ONE device exactly once even while
    waves migrate between backlogged lanes: dispatched_items (which
    counts unique reals, not pads) equals the distinct items submitted."""
    rng = random.Random(71)
    pipe, locals_, remotes = _fed_pipe(
        2, 1, PIPELINE_STEAL_THRESHOLD=4, PIPELINE_STEAL_COOLDOWN=0.0)
    n_items = 0
    toks = []
    for i in range(30):
        t = pipe.submit_verify(_junk_items(rng, 5), lane=0)
        t.lane_hint = None          # eligible: only the PIN blocks a steal
        toks.append(t)
        n_items += 5
    pipe._balance()
    assert pipe.stats["steals"] >= 1, "backlog never migrated"
    for t in toks:
        out = pipe.collect_verify(t, wait=True)
        assert out is not None and len(out) == 5
    assert pipe.stats["dispatched_items"] == n_items, \
        "a stolen item was dispatched more (or less) than once"
    assert pipe.stats["stolen_items"] >= 1
    # the remote lane really absorbed work
    assert pipe.lanes[2].stats["dispatched_items"] >= 1


def test_federated_pinned_placement_honored():
    """place() maps pinned shard tags onto LOCAL chips only, and a
    pinned token never migrates off its chip — a backlogged pinned lane
    keeps its own queue (its fallback chain is its own supervisor)."""
    rng = random.Random(73)
    pipe, _, _ = _fed_pipe(2, 2, PIPELINE_STEAL_THRESHOLD=1,
                           PIPELINE_STEAL_COOLDOWN=0.0)
    assert [pipe.place(t) for t in range(5)] == [0, 1, 0, 1, 0]
    toks = [pipe.submit_verify(_junk_items(rng, 2), lane=0)
            for _ in range(40)]
    pre = pipe._lane_backlog(pipe.lanes[0])
    pipe._balance()
    assert pipe.stats["steals"] == 0, "a pinned token migrated"
    assert pipe._lane_backlog(pipe.lanes[0]) == pre
    for t in toks:
        assert pipe.collect_verify(t, wait=True) is not None
    assert pipe.lanes[0].stats["dispatched_items"] == 80
    assert all(l.stats["dispatched_items"] == 0 for l in pipe.lanes[1:])


def test_federated_steal_hysteresis_never_oscillates():
    """Symmetric load on two lanes: neither clears the occupancy-delta
    threshold, so zero steals — and after a genuine steal the per-pair
    cooldown blocks the immediate reverse flow (anti-flap)."""
    rng = random.Random(79)
    pipe, _, _ = _fed_pipe(2, 0, PIPELINE_STEAL_THRESHOLD=8,
                           PIPELINE_STEAL_COOLDOWN=60.0)
    for i in range(20):                     # 20 items each, symmetric
        for lane in (0, 1):
            t = pipe.submit_verify(_junk_items(rng, 1), lane=lane)
            t.lane_hint = None
    for _ in range(50):
        pipe._balance()
    assert pipe.stats["steals"] == 0, "symmetric load oscillated"
    # now a real imbalance: one steal fires, the echo is suppressed
    for i in range(30):
        t = pipe.submit_verify(_junk_items(rng, 1), lane=0)
        t.lane_hint = None
    pipe._balance()
    assert pipe.stats["steals"] == 1
    # tilt the load the OTHER way: the delta now clears the threshold in
    # reverse, but the per-pair cooldown must hold the echo (anti-flap)
    for i in range(30):
        t = pipe.submit_verify(_junk_items(rng, 1), lane=1)
        t.lane_hint = None
    for _ in range(50):
        pipe._balance()
    assert pipe.stats["steals"] == 1, "steal echoed back within cooldown"


def test_federated_breaker_evacuates_to_local_lanes():
    """An open remote breaker evacuates that lane's queue back to
    HOST-LOCAL lanes unconditionally (no threshold, no cooldown) — the
    crypto_host_down steal-back contract."""
    import types
    rng = random.Random(83)
    pipe, locals_, remotes = _fed_pipe(2, 1, PIPELINE_STEAL_THRESHOLD=10 ** 6,
                                       PIPELINE_STEAL_COOLDOWN=60.0)
    # queue unhinted work onto the remote lane directly
    for i in range(10):
        t = pipe.submit_verify(_junk_items(rng, 2))
        t.lane_hint = None
    # drain whatever landed locally so only the remote queue remains
    remote = pipe.lanes[2]
    for lane in pipe.lanes[:2]:
        lane.staged.clear()
        lane.first_staged = None
    if not remote.staged:                   # ensure the remote has work
        t = pipe.submit_verify(_junk_items(rng, 2))
        t.lane_hint = None
        remote.staged.append(t)
    remotes[0].breaker = types.SimpleNamespace(state="open")
    pre = pipe._lane_backlog(remote)
    assert pre > 0
    pipe._balance()
    assert pipe._lane_backlog(remote) == 0, "open lane kept its queue"
    assert sum(pipe._lane_backlog(l) for l in pipe.lanes[:2]) == pre
    assert pipe.stats["steals"] >= 1


def test_federated_idle_dead_host_rejoins_via_pump():
    """Placement routes AROUND an open lane and evacuation empties its
    queue, so a dead host's supervisor sees no traffic at all — nothing
    on the submit/collect path would ever run its probe. The ring pump
    must drive recovery itself (service() -> supervisor.pump_recovery):
    after the host heals, pumping ALONE re-closes the breaker (re-warm
    included) and fresh waves reach the host again."""
    from plenum_tpu.crypto.ed25519 import CpuEd25519Verifier
    from plenum_tpu.parallel.federation import FederatedCryptoPipeline
    from plenum_tpu.parallel.supervisor import (CLOSED, CircuitBreaker,
                                                SupervisedVerifier)

    class DyingHost(CpuEd25519Verifier):
        def __init__(self):
            super().__init__()
            self.dead = False
            self.rewarms = 0

        def rewarm(self):
            if self.dead:
                raise ConnectionError("host down")
            self.rewarms += 1

        def submit_batch(self, items):
            if self.dead:
                raise ConnectionError("host down")
            return super().submit_batch(items)

    clock = [0.0]
    host = DyingHost()
    sup = SupervisedVerifier(
        host, fallback=CpuEd25519Verifier(),
        breaker=CircuitBreaker(fail_threshold=1, cooldown=1.0,
                               now=lambda: clock[0]),
        now=lambda: clock[0], label="remote0")
    pipe = FederatedCryptoPipeline(
        ed_inners=[FakeDeviceVerifier() for _ in range(2)],
        remote_inners=[sup], hosts=["/tmp/fake0.sock"],
        config=_fast_config(PIPELINE_STEAL_THRESHOLD=10 ** 6,
                            PIPELINE_STEAL_COOLDOWN=60.0),
        threaded=False)
    remote = pipe.lanes[2]
    rng = random.Random(97)

    def through_remote(n):
        t = pipe.submit_verify(_junk_items(rng, n))
        t.lane_hint = None
        for lane in pipe.lanes:
            if t in lane.staged:
                lane.staged.remove(t)
                if not lane.staged:
                    lane.first_staged = None
        remote.staged.append(t)
        if remote.first_staged is None:
            remote.first_staged = clock[0]
        return t

    # the host dies with one wave headed its way: the supervisor falls
    # back (the wave still settles) and the breaker opens
    host.dead = True
    tok = through_remote(2)
    pipe.service(force=True)
    assert pipe.collect_verify(tok, wait=True) is not None
    assert sup.breaker.state != CLOSED
    assert remote.degraded()

    # heal, then pump service() with ZERO traffic anywhere: recovery
    # must come from the pump, not from batches the lane never gets
    host.dead = False
    clock[0] += 2.0                       # past the cooldown
    for _ in range(4):
        pipe.service()
    assert sup.breaker.state == CLOSED, \
        "idle open lane never probed: pump_recovery not driven"
    assert host.rewarms >= 1, "re-admission skipped the re-warm"
    assert not remote.degraded()

    # rejoin is real: a fresh wave through the lane hits the device path
    dev_before = sup.stats["device_batches"]
    tok = through_remote(2)
    pipe.service(force=True)
    assert pipe.collect_verify(tok, wait=True) is not None
    assert sup.stats["device_batches"] > dev_before


def test_federated_zero_remote_constructs_pr14_class_exactly():
    """PIPELINE_REMOTE_HOSTS unset -> the construction seam returns the
    PR 14 classes THEMSELVES (no federation subclass anywhere on the
    hot path), and the federated subclass's pump overhead with zero
    remotes stays within noise of the PR 14 ring (microbench pin)."""
    from plenum_tpu.parallel.federation import FederatedCryptoPipeline
    from plenum_tpu.parallel.pipeline import MultiDeviceCryptoPipeline
    assert Config().PIPELINE_REMOTE_HOSTS == ""
    p1 = make_crypto_pipeline(Config(PIPELINE_DEVICES=1), "jax")
    assert type(p1) is CryptoPipeline
    p2 = make_crypto_pipeline(Config(PIPELINE_DEVICES=2), "jax")
    assert type(p2) is MultiDeviceCryptoPipeline
    assert not isinstance(p2, FederatedCryptoPipeline)
    p2.close()
    # hosts set -> the factory takes the federation branch, which fails
    # FAST on an unreachable roster entry (operator error, not a silent
    # single-host fallback)
    with pytest.raises((OSError, RuntimeError)):
        make_crypto_pipeline(
            Config(PIPELINE_DEVICES=1,
                   PIPELINE_REMOTE_HOSTS="/tmp/nonexistent-fed.sock"),
            "jax")

    def drive(pipe, n_ops=60):
        rng = random.Random(89)
        t0 = time.perf_counter()
        for _ in range(n_ops):
            tok = pipe.submit_verify(_junk_items(rng, 8))
            pipe.collect_verify(tok, wait=True)
        return (time.perf_counter() - t0) / n_ops

    base, _ = _multi_pipe(2)
    fed, _, _ = _fed_pipe(2, 0)
    drive(base, 10)
    drive(fed, 10)
    per_base = drive(base)
    per_fed = drive(fed)
    assert per_fed < per_base * 3 + 1e-3, \
        f"zero-remote federation {per_fed * 1e6:.0f}us/op vs PR 14 " \
        f"{per_base * 1e6:.0f}us/op"


# --- the ring's host phases on the profiler's clock --------------------------

def _host_spans(log_dir, prefix):
    """{span name: count} of the trace's host events named `prefix`*."""
    from collections import Counter
    from benchmarks.trace_reduce import (DEVICE_PLANE_PREFIX, find_xplane,
                                         xplane_events)
    return Counter(
        name for plane, _line, name, _start, _dur
        in xplane_events(find_xplane(str(log_dir)))
        if not plane.startswith(DEVICE_PLANE_PREFIX)
        and name.startswith(prefix))


@pytest.mark.parametrize("lanes", [0, 2], ids=["single_ring", "two_lanes"])
def test_ring_phases_are_spans_of_a_held_trace(tmp_path, lanes):
    """While a jax.profiler trace is held, every wave leaves ring.pack,
    ring.dispatch and ring.collect on the trace's host plane (beside the
    benchmark's own window span: trace_reduce reads only that one, so the
    device numbers of a traced run read as before); with no trace held
    the same calls record nothing and the ring answers the same."""
    import jax
    rng = random.Random(91)
    if lanes:
        pipe, _ = _multi_pipe(lanes)
    else:
        pipe = CryptoPipeline(ed_inner=FakeDeviceVerifier(),
                              config=_fast_config())

    def waves(n):
        for _ in range(n):
            tok = pipe.submit_verify(_junk_items(rng, 8))
            assert pipe.collect_verify(tok, wait=True).all()

    waves(2)                            # no trace held: nothing recorded
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench_trace_window"):
            waves(3)
    finally:
        jax.profiler.stop_trace()
    assert pipe.stats["dispatches"] == 5
    spans = _host_spans(tmp_path, "ring.")
    assert spans == {"ring.pack": 3, "ring.dispatch": 3, "ring.collect": 3}
    assert _host_spans(tmp_path, "bench_trace_window") == {
        "bench_trace_window": 1}
