"""The NEW_VIEW's selection reads each vote once and still selects what the
plain rule selects.

`NewViewBuilder.calc_batches` indexes every vote's `prepared` and
`preprepared` lists by pp_seq_no once. The rule it replaced re-parsed every
list for every sequence number; it is kept here, word for word, as the
reference: same list of batches, order and tie-breaks included, on seeded
vote sets, and a `BatchID.from_seq` count equal to the votes' length."""
from __future__ import annotations

import random
from typing import Optional

import pytest

from plenum_tpu.common.node_messages import ViewChange
from plenum_tpu.consensus.batch_id import BatchID
from plenum_tpu.consensus.consensus_shared_data import ConsensusSharedData
from plenum_tpu.consensus.view_change_service import (NewViewBuilder,
                                                      view_change_digest)

VALIDATORS = ["Alpha", "Beta", "Gamma", "Delta"]


class PlainBuilder:
    """The selection as it was before the votes were indexed (quadratic in
    the batches a vote carries)."""

    def __init__(self, data: ConsensusSharedData):
        self._data = data

    def calc_checkpoint(self, vcs) -> Optional[tuple]:
        best: Optional[tuple] = None
        for vc in vcs:
            for cp in vc.checkpoints:
                cp = tuple(cp)
                end = cp[2]
                usable = sum(1 for v in vcs if end >= v.stable_checkpoint)
                if not self._data.quorums.strong.is_reached(usable):
                    continue
                holders = sum(1 for v in vcs
                              if cp in {tuple(c) for c in v.checkpoints})
                if not self._data.quorums.weak.is_reached(holders):
                    continue
                if best is None or end > best[2]:
                    best = cp
        return best

    def calc_batches(self, cp: tuple, vcs) -> Optional[list[BatchID]]:
        batches: list[BatchID] = []
        pp_seq_no = cp[2] + 1
        while pp_seq_no <= cp[2] + self._data.log_size:
            bid = self._find_batch(vcs, pp_seq_no)
            if bid is not None:
                batches.append(bid)
                pp_seq_no += 1
                continue
            if self._null_batch_certified(vcs, pp_seq_no):
                break
            return None
        return batches

    def _find_batch(self, vcs, pp_seq_no) -> Optional[BatchID]:
        best: Optional[BatchID] = None
        for vc in vcs:
            for raw in vc.prepared:
                bid = BatchID.from_seq(raw)
                if bid.pp_seq_no != pp_seq_no:
                    continue
                if best is not None and (bid.view_no, bid.pp_view_no,
                                         bid.pp_digest) <= \
                        (best.view_no, best.pp_view_no, best.pp_digest):
                    continue
                if (self._prepared_certified(bid, vcs)
                        and self._preprepared_certified(bid, vcs)):
                    best = bid
        return best

    def _prepared_certified(self, bid: BatchID, vcs) -> bool:
        def not_contradicting(vc: ViewChange) -> bool:
            if bid.pp_seq_no <= vc.stable_checkpoint:
                return False
            for raw in vc.prepared:
                other = BatchID.from_seq(raw)
                if other.pp_seq_no != bid.pp_seq_no:
                    continue
                if other.view_no > bid.view_no:
                    return False
                if other.view_no >= bid.view_no and (
                        other.pp_digest != bid.pp_digest
                        or other.pp_view_no != bid.pp_view_no):
                    return False
            return True
        return self._data.quorums.strong.is_reached(
            sum(1 for vc in vcs if not_contradicting(vc)))

    def _preprepared_certified(self, bid: BatchID, vcs) -> bool:
        def witnessed(vc: ViewChange) -> bool:
            for raw in vc.preprepared:
                other = BatchID.from_seq(raw)
                if (other.pp_seq_no == bid.pp_seq_no
                        and other.pp_view_no == bid.pp_view_no
                        and other.pp_digest == bid.pp_digest
                        and other.view_no >= bid.view_no):
                    return True
            return False
        return self._data.quorums.weak.is_reached(
            sum(1 for vc in vcs if witnessed(vc)))

    def _null_batch_certified(self, vcs, pp_seq_no) -> bool:
        def has_no_prepare(vc: ViewChange) -> bool:
            if pp_seq_no <= vc.stable_checkpoint:
                return False
            return all(BatchID.from_seq(raw).pp_seq_no != pp_seq_no
                       for raw in vc.prepared)
        return self._data.quorums.strong.is_reached(
            sum(1 for vc in vcs if has_no_prepare(vc)))


# --- seeded vote sets --------------------------------------------------------

CP_END = 100
CHECKPOINT = (0, 1, CP_END, "cp100")

SCENARIOS = ("clean", "lagging_tails", "conflicting_digests", "mixed_views",
             "stable_checkpoints_differ", "null_gap", "hole")


def _ids_at(seq: int, voters: int, scenario: str, rng) -> list[list]:
    """What each voter lists at `seq`: -> one list of batch ids a voter."""
    common = (0, 0, seq, f"d{seq}")
    ids = [[common] for _ in range(voters)]
    if scenario == "conflicting_digests" and rng.random() < 0.4:
        # one voter holds another digest at this seq, alone or beside the
        # common one, before it or after it
        other = (0, 0, seq, f"x{seq}")
        ids[rng.randrange(voters)] = rng.choice(
            [[other], [common, other], [other, common]])
    if scenario == "mixed_views":
        # the batch was re-ordered in view 1: some voters hold the view-1
        # certificate, some still the view-0 one, some both; now and then
        # a batch first proposed in view 1 stands beside it on one voter
        roll = rng.random()
        if roll < 0.6:
            for v in range(voters):
                ids[v] = rng.choice([[(1, 0, seq, f"d{seq}")], [common],
                                     [common, (1, 0, seq, f"d{seq}")],
                                     [(1, 0, seq, f"d{seq}"), common]])
        elif roll < 0.75:
            ids[rng.randrange(voters)].append((1, 1, seq, f"v1-{seq}"))
    return ids


def _votes(voters: int, batches: int, scenario: str, seed: int) -> list:
    """`voters` votes for view 2 over `batches` batches past CHECKPOINT, in
    author-sorted order as the service hands them to the builder."""
    rng = random.Random(seed)
    seqs = list(range(CP_END + 1, CP_END + 1 + batches))
    gap = rng.choice(seqs) if seqs and scenario == "null_gap" else None
    hole = rng.choice(seqs) if seqs and scenario == "hole" else None
    listed = {seq: _ids_at(seq, voters, scenario, rng) for seq in seqs}
    votes = []
    for v in range(voters):
        prepared, preprepared = [], []
        stable = CP_END
        checkpoints = [CHECKPOINT]
        held = seqs
        if scenario == "lagging_tails" and seqs:
            held = seqs[:rng.randint(max(0, batches - 6), batches)]
        if scenario == "stable_checkpoints_differ":
            # one voter stabilized a later checkpoint (it counts for no
            # batch at or below it), one an earlier one
            if v == 0:
                stable = CP_END + min(batches, 5)
                checkpoints = [CHECKPOINT, (0, CP_END + 1, stable, "later")]
            elif v == 1:
                stable = 0
                checkpoints = [(0, 0, 0, "initial"), CHECKPOINT]
        for seq in held:
            ids = listed[seq][v]
            if seq == gap or (seq == hole and v != 0):
                ids = []
            preprepared.extend(ids)
            if scenario == "lagging_tails" and seq == held[-1] \
                    and rng.random() < 0.5:
                continue                     # pre-prepared, not prepared
            prepared.extend(ids)
        votes.append(ViewChange(
            view_no=2, stable_checkpoint=stable,
            prepared=tuple(prepared), preprepared=tuple(preprepared),
            checkpoints=tuple(checkpoints)))
    return votes


CASES = [(voters, batches, scenario)
         for voters in (3, 4)
         for scenario in SCENARIOS
         for batches in (0, 1, 17, 90)] + \
        [(voters, 300, scenario) for voters in (3, 4)
         for scenario in ("clean", "lagging_tails")]


@pytest.mark.parametrize(
    "voters,batches,scenario", CASES,
    ids=[f"{v}voters-{b}batches-{s}" for v, b, s in CASES])
def test_indexed_selection_is_the_plain_selection(voters, batches, scenario,
                                                  monkeypatch):
    data = ConsensusSharedData("Beta:0", VALIDATORS, 0)
    plain, indexed = PlainBuilder(data), NewViewBuilder(data)
    seen_none = seen_some = False
    for seed in range(5 if batches < 300 else 2):
        vcs = _votes(voters, batches, scenario, 1000 * batches + seed)
        cp = plain.calc_checkpoint(vcs)
        assert indexed.calc_checkpoint(vcs) == cp
        # three votes of which one stabilized later agree on no checkpoint;
        # the batches are selected over the votes all the same
        assert cp is not None or (
            voters == 3 and scenario == "stable_checkpoints_differ")
        cp = cp or CHECKPOINT
        want = plain.calc_batches(cp, vcs)

        parsed = []
        real = BatchID.from_seq.__func__
        monkeypatch.setattr(
            BatchID, "from_seq",
            classmethod(lambda cls, raw: parsed.append(1) or real(cls, raw)))
        got = indexed.calc_batches(cp, vcs)
        monkeypatch.undo()

        assert got == want, (seed, scenario)
        assert len(parsed) == sum(len(vc.prepared) + len(vc.preprepared)
                                  for vc in vcs)
        seen_none |= want is None
        seen_some |= bool(want)
    # three votes select only what all three bear out; of four, one may
    # differ, and a batch that one alone prepared reads as a gap
    if scenario == "hole" and batches and voters == 3:
        assert seen_none         # one witness of a prepared batch: no NEW_VIEW
    if batches > 1 and (voters == 4 or scenario in (
            "clean", "lagging_tails", "null_gap")):
        assert seen_some
    if scenario == "clean":
        assert want == [BatchID(0, 0, s, f"d{s}")
                        for s in range(CP_END + 1, CP_END + 1 + batches)]


def test_tie_breaks_follow_the_votes_and_their_lists():
    """Two certificates at one sequence number, equal in views: the higher
    digest wins wherever it stands, as in the plain rule."""
    data = ConsensusSharedData("Beta:0", VALIDATORS, 0)
    a, b = (0, 0, 101, "aaa"), (0, 0, 101, "bbb")

    def vote(*ids):
        return ViewChange(view_no=1, stable_checkpoint=CP_END,
                          prepared=tuple(ids), preprepared=(a, b),
                          checkpoints=(CHECKPOINT,))
    for vcs in ([vote(a), vote(), vote(), vote(b)],
                [vote(b), vote(), vote(), vote(a)],
                [vote(a, b), vote(), vote(b, a)],
                [vote(b, a), vote(a), vote(b)]):
        want = PlainBuilder(data).calc_batches(CHECKPOINT, vcs)
        assert NewViewBuilder(data).calc_batches(CHECKPOINT, vcs) == want


def test_a_vote_is_digested_once(monkeypatch):
    """The digest of a vote is kept on the vote: the acks, the citations of
    a NEW_VIEW and their check read the kept one; a vote that replaces it
    is another object, with a digest of its own."""
    from plenum_tpu.consensus import view_change_service as vcs_mod
    dumped = []
    real = vcs_mod.json_dumps
    monkeypatch.setattr(vcs_mod, "json_dumps",
                        lambda d: dumped.append(1) or real(d))
    vc = _votes(3, 90, "clean", 7)[0]
    first = view_change_digest(vc)
    assert [view_change_digest(vc) for _ in range(5)] == [first] * 5
    assert len(dumped) == 1
    import dataclasses
    other = dataclasses.replace(vc, stable_checkpoint=CP_END - 100)
    assert view_change_digest(other) != first
    assert len(dumped) == 2
    # equal content, equal digest, whatever object carries it
    assert view_change_digest(ViewChange.from_dict(vc.to_dict())) == first
    assert "_digest" not in vc.to_dict()
