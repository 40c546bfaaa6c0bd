"""The comparison that decides `correct`: values, roots and quorum sizes,
each beside its limit. Every comparison is exact, so every limit is 0
(or a least quorum). Pure functions of what the run observed; the numbers
they are given come from the topology, the reference and the Tracker."""
from __future__ import annotations

import json


class Checks:
    def __init__(self):
        self.rows: list[dict] = []

    def add(self, name: str, got, limit, ok: bool, note: str = "") -> None:
        self.rows.append({"check": name, "got": got, "limit": limit,
                          "ok": bool(ok), **({"note": note} if note else {})})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def say(self) -> None:
        for r in self.rows:
            print(json.dumps({"compared": r}), flush=True)


def nodes_agree(checks: Checks, states: list) -> None:
    """1. The nodes' domain ledger size and root, state root and audit
    root are equal."""
    views = {(s["domain_size"], s["domain_root"], s["state_root"],
              s["audit_root"]) for s in states}
    checks.add("nodes.distinct_views", len(views), 1,
               len(views) == 1 and len(states) > 0,
               "" if len(views) == 1 else json.dumps(states))


def acknowledged_in_ledger(checks: Checks, states: list, preload_size: int,
                           n_acked: int, acked_results: dict,
                           ledger_txns: dict) -> None:
    """2. Ledger size >= preload + acknowledged writes, and every
    acknowledged write's transaction is in the ledger at its seqNo.
    acked_results may be a sample of the n_acked: the reference's root
    (3a), computed over the replies' transactions, covers the rest."""
    size = min((s["domain_size"] for s in states), default=0)
    checks.add("ledger.size_minus_preload_minus_acked",
               size - preload_size - n_acked, 0,
               size >= preload_size + n_acked)
    lost = 0
    for key, result in acked_results.items():
        seq = (result.get("txnMetadata") or {}).get("seqNo")
        have = ledger_txns.get(seq)
        meta = ((have or {}).get("txn") or {}).get("metadata") or {}
        if (meta.get("from"), meta.get("reqId")) != key:
            lost += 1
    checks.add("ledger.acknowledged_writes_lost", lost, 0, lost == 0)


def reference_agrees(checks: Checks, states: list, ref_root: bytes,
                     ref_size: int) -> None:
    """3a. The plain reference's RFC 6962 root over the ledger's
    transactions in order equals the pool's."""
    wrong = sum(1 for s in states if s["domain_root"] != ref_root.hex()
                or s["domain_size"] != ref_size)
    checks.add("reference.root_mismatches", wrong, 0,
               wrong == 0 and len(states) > 0)


def reads_agree(checks: Checks, reads: list, wanted: list) -> None:
    """3b. Every read returns the reference's value, with a proof and an
    n-f multi-signature that verified client-side from one reply."""
    unverified = sum(1 for ok, _ in reads if not ok)
    wrong = 0
    for (_, data), want in zip(reads, wanted):
        got = data.get("verkey") if isinstance(data, dict) else data
        wrong += got != want
    checks.add("reads.unverified", unverified, 0,
               unverified == 0 and len(reads) > 0)
    checks.add("reads.wrong_value", wrong, 0, wrong == 0)


def no_fallback(checks: Checks, problems: list,
                name: str = "plane.fallback_problems") -> None:
    """4. device_batches grew; no fallback counter, unpinned shape, cmt
    host fallback or executable count did; every breaker closed."""
    checks.add(name, len(problems), 0, not problems,
               "; ".join(problems))


def verdicts_agree(checks: Checks, device: list, cpu: list,
                   corrupted: int) -> None:
    """5. A sample of the window's signatures plus corrupted copies gives
    equal verdict vectors on the device path and on the CPU verifier."""
    differ = sum(1 for d, c in zip(device, cpu) if d != c) \
        + abs(len(device) - len(cpu))
    checks.add("verdicts.device_vs_cpu_differ", differ, 0,
               differ == 0 and len(device) > 0)
    rejected = sum(1 for d in device if not d)
    checks.add("verdicts.rejected_minus_corrupted", rejected - corrupted, 0,
               rejected == corrupted)


def quorum_held(checks: Checks, least: int, need: int) -> None:
    """6. Every acknowledgement rested on f+1 matching replies."""
    checks.add("acks.least_matching_replies", least, need, least >= need)
