"""Consistency-proof gathering: agree on the catchup target.

Reference behavior: plenum/server/catchup/cons_proof_service.py:24 — broadcast
our LedgerStatus; if n-f-1 peers answer with an equal status we are already
up to date; otherwise f+1 ConsistencyProofs naming the same (size, root)
fix the catchup target. The f+1 quorum suffices because at least one of the
proofs comes from an honest node, and the Merkle verification of the catchup
replies is what actually protects integrity.
"""
from __future__ import annotations

from typing import Callable, Optional

from plenum_tpu.common.backoff import ExponentialBackoff, RttEstimator
from plenum_tpu.common.node_messages import ConsistencyProof, LedgerStatus
from plenum_tpu.common.quorums import Quorums
from plenum_tpu.common.timer import TimerService
from plenum_tpu.execution.database_manager import DatabaseManager


class ConsProofService:
    def __init__(self, ledger_id: int, db: DatabaseManager,
                 quorums_provider: Callable[[], Quorums],
                 send: Callable,
                 on_target: Callable[[int, Optional[tuple[int, str, tuple[int, int]]]], None],
                 timer: Optional[TimerService] = None,
                 retry_timeout: float = 5.0,
                 config=None,
                 rtt: Optional[RttEstimator] = None,
                 salt: str = "",
                 on_unbacked: Optional[Callable[[int, int], None]] = None):
        """on_target(ledger_id, None) = already up to date;
        on_target(ledger_id, (size, root_hex, (view_no, pp_seq_no)));
        on_unbacked(ledger_id, size): a rejoining node's ledger runs past
        what f+1 validators hold, which ends at `size` (see start)."""
        self.ledger_id = ledger_id
        self._db = db
        self._quorums = quorums_provider
        self._send = send
        self._on_target = on_target
        self._on_unbacked = on_unbacked
        self._rejoin = False
        self._running = False
        self._timer = timer
        self._retry_timeout = retry_timeout
        # Adaptive re-request pacing: the first retry waits an
        # RTT-informed timeout (srtt + 4*rttvar, clamped), consecutive
        # fruitless retries back off exponentially with seeded jitter up
        # to CATCHUP_RETRY_MAX. A flat timeout is wrong in BOTH
        # directions — see common/backoff.py. Falls back to the flat
        # `retry_timeout` when CATCHUP_ADAPTIVE_TIMEOUTS is off.
        self._adaptive = bool(getattr(config, "CATCHUP_ADAPTIVE_TIMEOUTS",
                                      False)) if config is not None else False
        self._retry_min = getattr(config, "CATCHUP_RETRY_MIN", 0.25)
        self._retry_max = getattr(config, "CATCHUP_RETRY_MAX", 30.0)
        self._rtt = rtt if rtt is not None else RttEstimator()
        self._backoff = ExponentialBackoff(
            base=retry_timeout, cap=self._retry_max,
            jitter=0.3, salt=f"cons_proof/{salt}/{ledger_id}")
        self._sent_at: Optional[float] = None
        # the retry as armed: its delay, and the RTT base it was drawn on
        self._armed_delay = self._armed_base = retry_timeout
        self.rounds = 0          # status broadcasts this catchup round
        self._retry_armed = False
        self._same_status: set[str] = set()
        # rejoin: the sizes of the peers that hold a prefix of our ledger
        # or the whole of it, and the peers ahead of us
        self._held_by: dict[str, int] = {}
        self._ahead: set[str] = set()
        self._proofs: dict[tuple[int, str], set[str]] = {}
        # (size, root) -> {(view_no, pp_seq_no) -> voters}: the 3PC position
        # needs its own f+1 quorum — a single Byzantine peer echoing the
        # honest size/root must not get to pick the pool's 3PC key
        # (ref cons_proof_service.py _get_last_txn_3PC_key)
        self._last_3pc_votes: dict[tuple[int, str],
                                   dict[tuple[int, int], set[str]]] = {}

    def start(self, rejoin: bool = False) -> None:
        """rejoin: this node has just started from its disk and holds no
        3PC certificate for anything on it. Its own word is then not
        enough for the tail of its ledger: it is current only if f other
        validators hold that tail too (f+1 with itself, the number whose
        REPLYs acknowledge a write). A tail that n-f others lack can have
        been acknowledged to nobody, no quorum can be brought to it, and
        the pool will order other batches in its place: it is reported
        through on_unbacked, for the node to cut. With fewer answers the
        node waits and asks again: it cannot tell."""
        self._running = True
        self._rejoin = rejoin and self._on_unbacked is not None
        self._held_by.clear()
        self._ahead.clear()
        self._same_status.clear()
        self._proofs.clear()
        self._last_3pc_votes.clear()
        self._backoff.reset()
        self.rounds = 0
        self._broadcast_status()
        # re-broadcast until a quorum forms (the reference re-requests
        # consistency proofs): lost replies or peers that were mid-sync
        # when we asked must not stall this catchup forever — the leecher
        # has no other wakeup (found by the partition-heal fuzz: a second
        # catchup whose one-shot LedgerStatus went unanswered hung the
        # node in is_running=True with ordering paused)
        self._arm_retry()

    def _broadcast_status(self) -> None:
        ledger = self._db.get_ledger(self.ledger_id)
        self.rounds += 1
        if self._timer is not None:
            self._sent_at = self._timer.get_current_time()
        self._send(LedgerStatus(ledger_id=self.ledger_id,
                                txn_seq_no=ledger.size,
                                merkle_root=ledger.root_hash.hex(),
                                view_no=None, pp_seq_no=None), None)

    def _note_reply(self) -> None:
        """First answer to the outstanding broadcast: fold its round trip
        into the shared RTT estimate (later answers to the same broadcast
        measure peer spread, not the link — skip them). A retry armed on
        a longer estimate than the link now shows is brought forward: the
        status left before any round trip had been measured (a process
        just started: the 5 s fallback), and peers that answer in a
        millisecond but name no common target — a pool that orders moves
        between two answers — are asked again at the link's pace, from
        when the status left."""
        if self._sent_at is None or self._timer is None:
            return
        now, sent_at = self._timer.get_current_time(), self._sent_at
        self._rtt.note(now - sent_at)
        self._sent_at = None
        if not (self._adaptive and self._retry_armed and self._running):
            return
        base = self._rtt_base()
        if base < self._armed_base:
            delay = self._armed_delay * base / self._armed_base
            self._cancel_retry()
            self._timer.schedule(max(0.0, sent_at + delay - now),
                                 self._on_retry)
            self._retry_armed = True
            self._armed_base, self._armed_delay = base, delay

    def _rtt_base(self) -> float:
        return self._rtt.timeout(floor=self._retry_min, cap=self._retry_max,
                                 fallback=self._retry_timeout)

    def _retry_delay(self) -> float:
        if not self._adaptive:
            return self._retry_timeout
        self._armed_base = self._rtt_base()
        return self._backoff.next(base=self._armed_base)

    def _arm_retry(self) -> None:
        if self._timer is None:
            return
        self._cancel_retry()
        self._armed_delay = self._retry_delay()
        self._timer.schedule(self._armed_delay, self._on_retry)
        self._retry_armed = True

    def _cancel_retry(self) -> None:
        if self._retry_armed and self._timer is not None:
            self._timer.cancel(self._on_retry)
            self._retry_armed = False

    def _on_retry(self) -> None:
        self._retry_armed = False
        if not self._running:
            return
        self._broadcast_status()
        self._arm_retry()

    def stop(self) -> None:
        self._running = False
        self._cancel_retry()

    def process_ledger_status(self, msg: LedgerStatus, frm: str) -> None:
        """A peer telling us ITS status in response to ours."""
        if not self._running or msg.ledger_id != self.ledger_id:
            return
        self._note_reply()
        ledger = self._db.get_ledger(self.ledger_id)
        if msg.txn_seq_no <= ledger.size and \
                (msg.txn_seq_no < ledger.size or
                 msg.merkle_root == ledger.root_hash.hex()):
            self._same_status.add(frm)
            if self._rejoin:
                if msg.txn_seq_no and msg.merkle_root != \
                        ledger.tree.merkle_tree_hash(
                            0, msg.txn_seq_no).hex():
                    return               # not a prefix of ours: no witness
                self._held_by[frm] = msg.txn_seq_no
                if not self._tail_is_backed(ledger.size):
                    return
            if self._quorums().checkpoint.is_reached(len(self._same_status)):
                self._finish(None)       # n-f-1 peers agree we are current

    def _tail_is_backed(self, size: int) -> bool:
        """Rejoin: whether f other validators hold our ledger to its end.
        If n-f hold less, reports where f+1 of us end (the f-th longest
        of theirs) and stops: the node cuts its ledger and starts again."""
        f = self._quorums().f
        full = [p for p, held in self._held_by.items() if held >= size]
        if len(full) + len(self._ahead) >= f:
            return True
        if self._quorums().commit.is_reached(len(self._held_by)):
            backed = sorted(self._held_by.values(), reverse=True)[f - 1]
            self._running = False
            self._cancel_retry()
            self._on_unbacked(self.ledger_id, backed)
        return False

    def process_consistency_proof(self, msg: ConsistencyProof, frm: str) -> None:
        if not self._running or msg.ledger_id != self.ledger_id:
            return
        self._note_reply()
        ledger = self._db.get_ledger(self.ledger_id)
        if msg.seq_no_end <= ledger.size:
            return
        self._ahead.add(frm)
        key = (msg.seq_no_end, msg.new_merkle_root)
        self._proofs.setdefault(key, set()).add(frm)
        if msg.view_no is not None and msg.pp_seq_no is not None:
            self._last_3pc_votes.setdefault(key, {}).setdefault(
                (msg.view_no, msg.pp_seq_no), set()).add(frm)
        if self._quorums().consistency_proof.is_reached(len(self._proofs[key])):
            self._finish((key[0], key[1], self._quorumed_3pc(key)))

    def _quorumed_3pc(self, key) -> Optional[tuple[int, int]]:
        """Minimum 3PC key with f+1 matching non-None votes, else None
        (then catchup proceeds without adopting a 3PC position)."""
        quorum = self._quorums().weak
        quorumed = [pos for pos, voters in self._last_3pc_votes.get(key, {}).items()
                    if quorum.is_reached(len(voters))]
        return min(quorumed) if quorumed else None

    def _finish(self, target) -> None:
        self._running = False
        self._cancel_retry()
        self._on_target(self.ledger_id, target)
