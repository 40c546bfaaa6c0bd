"""(e) The plain reference's Merkle root against a hand-computed RFC 6962
case, and `correct` turning false on a perturbed reply, on a root mismatch
and on a grown fallback counter."""
import hashlib

import pytest

import bench_paths  # noqa: F401
from benchmarks import correctness, reference


def h(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def test_rfc6962_by_hand():
    d = [b"", b"\x00", b"\x10", b"\x20\x21", b"\x30\x31", b"\x40\x41\x42\x43",
         b"\x50\x51\x52\x53\x54\x55\x56\x57"]
    leaf = [h(b"\x00" + x) for x in d]
    node = lambda a, b: h(b"\x01" + a + b)      # noqa: E731
    assert reference.merkle_root([]) == h(b"")
    assert reference.merkle_root(d[:1]) == leaf[0]
    # RFC 6962's own test vectors for these seven leaves
    assert reference.merkle_root(d[:1]).hex() == \
        "6e340b9cffb37a989ca544e6bb780a2c78901d3fb33738768511a30617afa01d"
    assert reference.merkle_root(d).hex() == \
        "ddb89be403809e325750d3d263cd78929c2942b7942a34b77e122c9594a74c8c"
    # n = 3: split 2 + 1; n = 5: split 4 + 1; n = 7: 4 + (2 + 1)
    assert reference.merkle_root(d[:3]) == node(node(leaf[0], leaf[1]),
                                                leaf[2])
    four = node(node(leaf[0], leaf[1]), node(leaf[2], leaf[3]))
    assert reference.merkle_root(d[:5]) == node(four, leaf[4])
    assert reference.merkle_root(d) == node(
        four, node(node(leaf[4], leaf[5]), leaf[6]))


def txn(kind, seq, **data):
    return {"txn": {"type": kind, "data": data, "metadata": {}},
            "txnMetadata": {"seqNo": seq}, "ver": "1"}


def test_replay_builds_the_maps():
    txns = [txn("1", 1, dest="A", verkey="va"),
            txn("100", 2, dest="A", raw='{"endpoint": {"ha": "x"}}'),
            txn("1", 3, dest="B", verkey="vb"),
            txn("100", 4, dest="A", raw='{"endpoint": {"ha": "y"}}')]
    root, state = reference.replay(txns)
    assert state.verkeys == {"A": "va", "B": "vb"} and state.size == 4
    assert state.attrs[("A", "endpoint")] == '{"endpoint": {"ha": "y"}}'
    assert root == reference.merkle_root(reference.leaf_bytes(t)
                                         for t in txns)
    # the leaf encoding sorts map keys: insertion order does not matter
    assert reference.leaf_bytes({"b": 1, "a": {"d": 2, "c": 3}}) == \
        reference.leaf_bytes({"a": {"c": 3, "d": 2}, "b": 1})


STATE = {"node": "Node1", "domain_size": 3, "domain_root": "aa",
         "state_root": "bb", "audit_root": "cc"}


def sound() -> correctness.Checks:
    c = correctness.Checks()
    correctness.nodes_agree(c, [STATE, dict(STATE, node="Node2")])
    correctness.reference_agrees(c, [STATE], bytes.fromhex("aa"), 3)
    correctness.reads_agree(c, [(True, {"verkey": "va"}), (True, "raw")],
                            ["va", "raw"])
    correctness.no_fallback(c, [])
    correctness.verdicts_agree(c, [True, False], [True, False], 1)
    correctness.quorum_held(c, 2, 2)
    return c


def test_sound_observations_are_correct():
    c = sound()
    assert c.correct and all(r["limit"] in (0, 1, 2) for r in c.rows)
    assert not correctness.Checks().correct     # nothing compared: not correct


@pytest.mark.parametrize("break_it", [
    lambda c: correctness.reads_agree(c, [(True, {"verkey": "FORGED"})],
                                      ["va"]),
    lambda c: correctness.reads_agree(c, [(False, {"verkey": "va"})], ["va"]),
    lambda c: correctness.reference_agrees(c, [STATE],
                                           bytes.fromhex("ab"), 3),
    lambda c: correctness.nodes_agree(
        c, [STATE, dict(STATE, state_root="other")]),
    lambda c: correctness.verdicts_agree(c, [True, True], [True, False], 1),
    lambda c: correctness.quorum_held(c, 1, 2),
    lambda c: correctness.acknowledged_in_ledger(
        c, [STATE], 1, 2, {("A", 7): {"txnMetadata": {"seqNo": 2}}},
        {2: {"txn": {"metadata": {"from": "A", "reqId": 8}}}}),
], ids=["perturbed_reply", "unverified_read", "root_mismatch",
        "nodes_disagree", "forged_signature_accepted",
        "acknowledged_on_one_reply",
        "acknowledged_write_not_in_ledger"])
def test_each_fault_turns_correct_false(break_it):
    c = sound()
    break_it(c)
    assert not c.correct


def test_grown_fallback_counter_is_not_correct():
    from benchmarks.cell import window_failures
    lane = {"device_batches": 10, "breaker_state": "closed",
            "fallback_batches": 0, "hedge_wins": 0}
    after = dict(lane, device_batches=25)
    assert window_failures("w", [lane], [after], {"executables": 0}) == []
    for grown in (dict(after, fallback_batches=1), dict(after, hedge_wins=2),
                  dict(after, breaker_state="open"),
                  dict(lane)):                  # no device batch at all
        problems = window_failures("w", [lane], [grown], {})
        c = sound()
        correctness.no_fallback(c, problems)
        assert problems and not c.correct
    assert window_failures("w", [lane], [after], {"executables": 1})
    assert window_failures("w", [], [], {})     # no supervised plane
