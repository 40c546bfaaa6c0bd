"""Seeder: serve ledger status, consistency proofs, and catchup ranges.

Reference behavior: plenum/server/catchup/seeder_service.py:14 — every node
answers peers' LedgerStatus with either its own status (peer is current) or a
ConsistencyProof from the peer's size to ours; answers CatchupReq with the
requested txn range plus the Merkle consistency proof that lets the leecher
verify the range against the agreed target root (process_catchup_req:49).
"""
from __future__ import annotations

import time
from typing import Callable

from plenum_tpu.common import tracing
from plenum_tpu.common.metrics import MetricsName, span_report
from plenum_tpu.common.node_messages import (CatchupRep, CatchupReq,
                                             ConsistencyProof, LedgerStatus)
from plenum_tpu.common.serialization import unpack
from plenum_tpu.execution.database_manager import DatabaseManager


class SeederService:
    def __init__(self, db: DatabaseManager,
                 send: Callable,
                 last_3pc: Callable[[], tuple[int, int]],
                 max_batch: int = 50, metrics=None):
        self._db = db
        self._send = send                     # send(msg, dst)
        self._last_3pc = last_3pc
        self._max_batch = max_batch
        self._metrics = metrics
        # a host span around each answer while a trace is held (the node
        # hands node.py's `_phase` in, as to its master's services)
        self.span = tracing.unspanned
        # what this node served to peers that catch up, since its start:
        # a seeder works on the loop that orders, so its seconds are taken
        # from the pool's writes (VALIDATOR_INFO `catchup.seeder`)
        self.stats = {"statuses": 0, "reqs": 0, "declined": 0,
                      "txns_served": 0, "bytes_served": 0}
        self._answers, self._answer_s = 0, 0.0

    def report(self) -> dict:
        acc = self._metrics.accumulators.get(MetricsName.SEEDER_SERVE_TIME) \
            if self._metrics is not None else None
        return dict(self.stats, serve=span_report(
            self._answers, self._answer_s,
            acc.samples if acc is not None else None))

    def _served(self, t0: float, txns: int = 0, nbytes: int = 0) -> None:
        """One answer left: its seconds, and what it carried."""
        took = time.perf_counter() - t0
        self._answers += 1
        self._answer_s += took
        self.stats["txns_served"] += txns
        self.stats["bytes_served"] += nbytes
        if self._metrics is not None:
            self._metrics.add_event(MetricsName.SEEDER_SERVE_TIME, took)
            if txns:
                self._metrics.add_event(MetricsName.SEEDER_TXNS_SERVED, txns)
                self._metrics.add_event(MetricsName.SEEDER_BYTES_SERVED,
                                        nbytes)

    def process_ledger_status(self, msg: LedgerStatus, frm: str) -> None:
        if msg.is_reply:
            return                    # an acknowledgment, not a status query
        ledger = self._db.get_ledger(msg.ledger_id)
        if ledger is None:
            return
        self.stats["statuses"] += 1
        self.span("seeder.serve", lambda: self._answer_status(msg, frm,
                                                              ledger))

    def _answer_status(self, msg: LedgerStatus, frm: str, ledger) -> None:
        t0 = time.perf_counter()
        view_no, pp_seq_no = self._last_3pc()
        if msg.txn_seq_no >= ledger.size:
            # peer is as current as us (or ahead): echo our own status
            self._send(LedgerStatus(
                ledger_id=msg.ledger_id, txn_seq_no=ledger.size,
                merkle_root=ledger.root_hash.hex(),
                view_no=view_no, pp_seq_no=pp_seq_no, is_reply=True), frm)
            self._served(t0)
            return
        proof = ledger.consistency_proof(msg.txn_seq_no, ledger.size) \
            if msg.txn_seq_no > 0 else []
        self._send(ConsistencyProof(
            ledger_id=msg.ledger_id,
            seq_no_start=msg.txn_seq_no,
            seq_no_end=ledger.size,
            view_no=view_no, pp_seq_no=pp_seq_no,
            old_merkle_root=msg.merkle_root,
            new_merkle_root=ledger.root_hash.hex(),
            hashes=tuple(proof)), frm)
        self._served(t0)

    def process_catchup_req(self, msg: CatchupReq, frm: str) -> None:
        ledger = self._db.get_ledger(msg.ledger_id)
        if ledger is None:
            return
        self.stats["reqs"] += 1
        if self._metrics is not None:
            self._metrics.add_event(MetricsName.SEEDER_REQS)
        self.span("seeder.serve", lambda: self._answer_req(msg, frm, ledger))

    def _answer_req(self, msg: CatchupReq, frm: str, ledger) -> None:
        t0 = time.perf_counter()
        if ledger.size < msg.catchup_till:
            # We cannot anchor a consistency proof at the leecher's agreed
            # target root (we don't have those txns yet), so any rep we send
            # would fail verification and get this honest node blacklisted.
            # Decline; the leecher's retry timer re-splits across other peers.
            self.stats["declined"] += 1
            return
        end = min(msg.seq_no_end, ledger.size, msg.seq_no_start + self._max_batch - 1)
        if end < msg.seq_no_start:
            self.stats["declined"] += 1
            return
        txns, nbytes = {}, 0
        for i in range(msg.seq_no_start, end + 1):
            raw = ledger.get_packed(i)
            nbytes += len(raw)
            txns[str(i)] = unpack(raw)
        # Ship the consistency proof from the chunk's end to the agreed
        # target size: after appending the chunk, the leecher's root at size
        # `end` plus this proof must reproduce the target root, which verifies
        # EVERY txn of the prefix (not just the last one).
        proof = ledger.consistency_proof(end, msg.catchup_till) \
            if msg.catchup_till > end else []
        self._send(CatchupRep(ledger_id=msg.ledger_id, txns=txns,
                              cons_proof=tuple(proof)), frm)
        self._served(t0, len(txns), nbytes)
