"""The `lane_per_chip` launcher, rehearsed on the CPU at test size (a host
verifier behind each validator's ring, benchmarks/node_entry.py): a sound
window is correct with FOUR supervisor records, the two controls are not
(acknowledge on the first reply; a validator that borrowed verdicts), and
nothing is left running. Labelled rehearsals: no metric is written."""
import json

import pytest

import bench_paths  # noqa: F401
from benchmarks import cell, reaper
from benchmarks.topologies import lane_per_chip

CELL = "pool4_lane_per_chip.write_flood"
NAMES = ["Node1", "Node2", "Node3", "Node4"]


def test_sound_run_four_owners_and_the_first_reply_control(tmp_path, capsys,
                                                           monkeypatch):
    marker = "lanetest" + tmp_path.name
    monkeypatch.setenv(reaper.MARKER_VAR, marker)
    result = tmp_path / "result.json"
    rc = cell.main(["--workload", CELL, "--seed", "2147483653",
                    "--seconds", "1.0", "--trace", "0", "--rehearse-cpu",
                    "--run-dir", str(tmp_path), "--result", str(result),
                    "--check", "2"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    got = json.loads(result.read_text())
    assert rc == 0 and got["correct"] is True and "rehearsal" in got
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["metrics"] == {}

    # every validator accounts for a plane of its own, before and after
    counters = next(x["counters"] for x in lines if "counters" in x)
    for side in ("before", "after"):
        for name in NAMES:
            assert f"plane.{name}.device_batches" in counters[side]
    grew = {n: counters["after"][f"plane.{n}.items_dispatched"]
            - counters["before"][f"plane.{n}.items_dispatched"]
            for n in NAMES}
    ordered = counters["after"]["consensus.ordered_writes"] \
        - counters["before"]["consensus.ordered_writes"]
    assert ordered > 0 and all(g >= ordered for g in grew.values()), grew
    assert counters["after"]["plane.unpinned_shapes"] == 0
    # ... and so do the four supervisor records cell.py judges
    _, sups = lane_per_chip.plane_counters(NAMES, [
        {"plane": {"ring": {"verify_items": 1, "dispatched_items": 1,
                            "verdict_cache_hits": 0, "dispatches": 1,
                            "unpinned_shapes": 0, "bucket_hit_rate": 1.0,
                            "cmt": {"host_fallbacks": 0}},
                   "supervisors": [{"device_batches": 1, "device_items": 64}],
                   "compile": {"executables": 0}},
         "ledgers": {"1": {"size": 5}}}] * 4)
    assert [s["label"] for s in sups] == NAMES

    compared = [x["compared"] for x in lines if "compared" in x]
    assert all(r["ok"] for r in compared)
    local = [r for r in compared if r["check"] == "plane.verdicts_are_local"]
    assert len(local) == 2 + 3 and all(r["limit"] == 0 for r in local)
    verdicts = [r for r in compared
                if r["check"] == "verdicts.device_vs_cpu_differ"]
    assert verdicts and verdicts[0]["got"] == 0
    controls = [x for x in lines if "control" in x]
    assert len(controls) == 3
    for c in controls:
        assert c["correct"] is False
        assert [r["check"] for r in c["failed_checks"]] == \
            ["acks.least_matching_replies"]
    assert reaper.marked(marker) == []          # nothing left running


def _counters(dispatched, cached, ordered):
    out = {}
    for name, d, c in zip(NAMES, dispatched, cached):
        out.update({f"plane.{name}.items_dispatched": d,
                    f"plane.{name}.cache_hits": c,
                    f"plane.{name}.ordered_writes": ordered,
                    "plane.executables": 8, "plane.unpinned_shapes": 0,
                    "plane.cmt_host_fallbacks": 0})
    return out


@pytest.mark.parametrize("dispatched, cached, short", [
    ([1000, 1000, 1000, 1000], [0, 0, 0, 0], {}),
    ([1000, 700, 1000, 1000], [0, 300, 0, 0], {}),      # its OWN cache
    ([1000, 1000, 250, 1000], [0, 0, 0, 0], {"Node3": 750}),
    ([250, 250, 250, 250], [0, 0, 0, 0],                # one shared verdict
     {n: 750 for n in NAMES}),
])
def test_a_borrowed_verdict_fails_verdicts_are_local(capsys, dispatched,
                                                     cached, short):
    """The second control: counters in which a validator dispatched and
    cached fewer signatures than it ordered writes, as behind a shared
    service, are not correct; cell.window_failures turns each entry of
    must_stay_zero that is not 0 into a problem."""
    before = _counters([0] * 4, [0] * 4, 16385)
    after = _counters(dispatched, cached, 16385 + 1000)
    assert lane_per_chip.local_shortfalls(NAMES, before, after) == short
    launcher = lane_per_chip.Launcher.__new__(lane_per_chip.Launcher)
    launcher.names = NAMES
    grew = {k: v for k, v in launcher.must_stay_zero(before, after).items()
            if v}
    assert sorted(grew.values()) == sorted(short.values())
    assert all(k.startswith("verdicts_are_local") for k in grew)
    row = json.loads(capsys.readouterr().out.splitlines()[-1])["compared"]
    assert row["check"] == "plane.verdicts_are_local" and row["limit"] == 0
    assert row["ok"] is (not short) and row["got"] == sum(short.values())
    problems = cell.window_failures(CELL, [], [], grew)
    assert len([p for p in problems if "verdicts_are_local" in p]) \
        == len(short)


def _signed(n, corrupt_last):
    from plenum_tpu.crypto.ed25519 import Ed25519Signer
    items = []
    for k in range(n):
        s = Ed25519Signer(seed=bytes([k + 1]) * 32)
        msg = b"ring sample %d" % k
        items.append((msg, s.sign(msg), s.verkey))
    for k in range(n - corrupt_last, n):
        msg, sig, vk = items[k]
        items[k] = (msg + b"!", sig, vk) if k % 2 else \
            (msg, bytes([sig[0] ^ 1]) + sig[1:], vk)
    return items


@pytest.mark.parametrize("lies", [False, True],
                         ids=["honest ring", "ring that answers True"])
def test_the_sample_is_judged_by_the_validators_own_ring(monkeypatch, lies):
    """Comparison 5 asks the ring the node was built with (node_entry's
    ring_verdicts): the window's signatures come from its verdict cache,
    the unseen ones go to its device in a pinned shape, and a ring whose
    answers are wrong is not correct."""
    import numpy as np

    from benchmarks import correctness, node_entry
    from plenum_tpu.config import load_config
    from plenum_tpu.crypto import ed25519
    from plenum_tpu.parallel.pipeline import make_crypto_pipeline
    double = node_entry.host_double()
    if lies:
        class Liar(double):
            def submit_batch(self, items):
                return np.ones(len(items), dtype=bool)
        double = Liar
    monkeypatch.setattr(ed25519, "JaxEd25519Verifier", double)
    ring = make_crypto_pipeline(load_config(), "jax")
    ring.prewarm(ring.quota_buckets())
    ring.pin()
    items = _signed(48, corrupt_last=8)
    ring.verifier().verify_batch(items[:32])        # "the window"
    got = node_entry.ring_verdicts(ring, items)
    assert got["ring"] == {"dispatched_items": 16, "verdict_cache_hits": 32,
                           "dispatches": 1, "unpinned_shapes": 0}
    assert lane_per_chip.ring_strayed(got) == {}
    cpu = [bool(v) for v in
           ed25519.CpuEd25519Verifier().verify_batch(items)]
    checks = correctness.Checks()
    correctness.verdicts_agree(checks, got["verdicts"], cpu, 8)
    assert checks.correct is (not lies)
    assert (got["verdicts"] == cpu) is (not lies)


SUP = {"device_batches": 7, "breaker_state": "closed"}
SOUND = {"verdicts": [True, False],
         "ring": {"dispatched_items": 2, "verdict_cache_hits": 0,
                  "dispatches": 1, "unpinned_shapes": 0},
         "supervisor": {"before": SUP,
                        "after": dict(SUP, device_batches=8)}}


@pytest.mark.parametrize("answers, want", [
    ([SOUND] * 4, [True, False]),
    # one validator's ring disagrees on the forged item
    ([SOUND] * 3 + [dict(SOUND, verdicts=[True, True])], [True, None]),
    # answered from a cache alone: nothing reached the device
    ([SOUND] * 3 + [dict(SOUND, ring=dict(SOUND["ring"], dispatches=0,
                                          dispatched_items=0))],
     [None, None]),
    ([dict(SOUND, ring=dict(SOUND["ring"], unpinned_shapes=1))]
     + [SOUND] * 3, [None, None]),
    # the supervisor answered on the CPU
    ([SOUND] * 2 + [dict(SOUND, supervisor={
        "before": SUP, "after": dict(SUP, device_batches=8,
                                     fallback_batches=1)})] + [SOUND],
     [None, None]),
    ([SOUND] * 3 + [dict(SOUND, supervisor={"before": SUP, "after": SUP})],
     [None, None]),
])
def test_four_rings_must_agree_and_answer_from_their_devices(
        tmp_path, capsys, answers, want):
    launcher = lane_per_chip.Launcher.__new__(lane_per_chip.Launcher)
    launcher.names, launcher.run_dir = NAMES, str(tmp_path)
    launcher.ctls = [str(tmp_path / n) for n in NAMES]
    posted = []
    launcher._post_to = lambda ctl, cmd, arg="": posted.append(cmd) or ctl
    launcher._answer = lambda done, wait: answers[launcher.ctls.index(done)]
    got = launcher.device_verdicts([(b"m", b"s" * 64, b"k" * 32)] * 2)
    assert got == want and posted == ["verdicts"] * 4
    told = [x for x in capsys.readouterr().out.splitlines()
            if "verdict_sample_not_from_the_ring" in x]
    assert len(told) == (1 if want == [None, None] else 0)


def test_a_job_runs_on_the_nodes_loop_not_on_the_control_thread():
    import threading
    import types

    from benchmarks import node_entry

    class FakeProdable:
        def prod(self):
            return 3
    fake = types.SimpleNamespace(
        build_node=lambda *a, **k: (FakeProdable(), "the node", None))
    node_entry.watch_build_node(fake)
    prodable, node, _ = fake.build_node("N1", "/nowhere")
    assert node == node_entry._node == "the node" and prodable.prod() == 3
    box = []

    def control():
        box.append(node_entry.on_node_loop(threading.get_ident))
        try:
            node_entry.on_node_loop(lambda: 1 // 0)
        except RuntimeError as e:
            box.append(str(e))
    t = threading.Thread(target=control)
    t.start()
    turns = 0
    while t.is_alive() and turns < 10_000:
        prodable.prod()
        turns += 1
        t.join(0.001)
    assert box[0] == threading.get_ident() != t.ident
    assert box[1].startswith("ZeroDivisionError")
    with pytest.raises(TimeoutError):       # a loop that never turns
        node_entry.on_node_loop(lambda: None, timeout=0.05)
    while not node_entry._jobs.empty():
        node_entry._jobs.get_nowait()


def test_the_flood_cell_runs_the_accepted_flood_on_another_deployment():
    """The four-chip cell is the co-hosted flood's mix and drive, number
    for number; BENCHMARK.json may give a pair of configuration and
    traffic once."""
    from benchmarks import manifest
    mine = manifest.cell("pool4_lane_per_chip.write_flood")
    accepted = manifest.cell("cohosted.write_flood")
    assert mine["traffic"] == accepted["traffic"]
    assert mine["entry"]["config"] != accepted["entry"]["config"]
    drive = [{k: v for k, v in c["workload"].items()
              if k not in ("name", "why")} for c in (mine, accepted)]
    assert drive[0] == drive[1]
    pairs = [(w["config"], w["traffic"])
             for w in manifest.benchmark()["workloads"]]
    assert len(set(pairs)) == len(pairs)


def _closed_loop_cells() -> list:
    from benchmarks import manifest
    return [w["name"] for w in manifest.benchmark()["workloads"]
            if manifest.cell(w["name"])["workload"]["drive"] == "closed_loop"]


@pytest.mark.parametrize("name", _closed_loop_cells())
def test_a_closed_loop_cells_device_sample_spans_a_whole_burst(name):
    """A closed loop works the device in bursts, one per agreed batch
    (~0.2-0.3 s apart), so the device sample of a traced run has to span a
    whole cycle. The served configuration's 25 ms fell between two bursts
    and read no device plane at all, which is why tcp_service.write_flood
    is not a cell (PERF.md, Open questions, 0a)."""
    from benchmarks import manifest
    assert manifest.cell(name)["config"]["trace_seconds"] >= 0.5
