"""(b) The generator is deterministic in --seed, an open loop's due times
do not depend on acknowledgements, every seed gets the same multiset of
work, and generator and drive kinds are found by name."""
import collections
import json

import pytest

import bench_paths  # noqa: F401
from benchmarks import manifest, traffic
from benchmarks.drives import open_loop

MIX = {"kind": "mixed_writes", "nym_share": 0.5,
       "attrib_raw_bytes": [64, 256]}
MOSTLY_ATTRIB = dict(MIX, nym_share=0.25)
BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("mix", [MIX, MOSTLY_ATTRIB], ids=["mixed", "mostly_attrib"])
def test_same_seed_same_stream(mix):
    a = traffic.plan(mix, BIG, 400, 4096)
    assert a == traffic.plan(mix, BIG, 400, 4096)
    assert a != traffic.plan(mix, BIG + 1, 400, 4096)


def test_mixed_writes_shape():
    ops = traffic.plan(MIX, 7, 1000, 4096)
    kinds = collections.Counter(op.kind for op in ops)
    assert kinds == {"NYM": 500, "ATTRIB": 500}
    signers = [op.signer for op in ops if op.kind == "ATTRIB"]
    assert len(set(signers)) == len(signers)        # without replacement
    assert len({op.target for op in ops if op.kind == "NYM"}) == 500
    for op in ops:
        if op.kind == "ATTRIB":
            assert 64 <= len(op.raw) <= 256
            assert list(json.loads(op.raw)) == ["endpoint"]
    with pytest.raises(SystemExit):
        traffic.plan(MIX, 7, 1000, 100)             # not enough signers


@pytest.mark.parametrize("seeds", [(1, 2), (3, BIG)])
def test_every_seed_gets_the_same_work(seeds):
    sizes = [sorted(len(op.raw) for op in traffic.plan(MIX, s, 600, 4096))
             for s in seeds]
    assert sizes[0] == sizes[1]
    drive = {"drive": "open_loop", "rate_per_s": 100}
    gaps = []
    for s in seeds:
        due = traffic.schedule(drive, s, 10.0)["due"]
        gaps.append(sorted(round(b - a, 9)
                           for a, b in zip(due, due[1:] + [10.0])))
    assert gaps[0] == gaps[1]


def test_open_loop_schedule_is_fixed_before_any_reply():
    drive = manifest.cell("tcp_service.write_steady")["workload"]
    due = open_loop.due_times(drive, 5, 20.0)
    assert len(due) == int(drive["rate_per_s"] * 20)
    assert traffic.schedule(drive, 5, 20.0) == {"due": due}
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 20.0
    # Poisson, not a metronome: gaps vary like an exponential's
    gaps = [b - a for a, b in zip(due, due[1:])]
    mean = sum(gaps) / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert 0.7 < var ** 0.5 / mean < 1.3
    assert traffic.stream_length(drive, 20.0) == len(due)


@pytest.mark.parametrize("drive, n, schedule", [
    ({"drive": "closed_loop", "in_flight": 256, "max_rate_per_s": 1500},
     1500 * 20 + 256, {"in_flight": 256}),
    ({"drive": "open_loop", "rate_per_s": 2.5}, 50, None)],
    ids=["closed_loop", "open_loop"])
def test_drive_kinds_are_found_by_name(drive, n, schedule):
    assert traffic.stream_length(drive, 20.0) == n
    got = traffic.schedule(drive, 9, 20.0)
    assert got == schedule if schedule else len(got["due"]) == n


@pytest.mark.parametrize("call", [
    lambda: traffic.plan({"kind": "no_such_generator"}, 1, 10, 100),
    lambda: traffic.schedule({"drive": "no_such_drive"}, 1, 1.0)],
    ids=["generator", "drive"])
def test_an_unknown_kind_is_an_error(call):
    with pytest.raises(SystemExit):
        call()
