"""The plain prefix check of a validator restarted under load.

It decides `rejoin.victim_disk_txns_differing_from_final` and
`rejoin.victim_disk_prefix_root_mismatches`: what the victim's domain txn
log held ON DISK at the kill (a copy taken before the restart, read by
reference_store.py) has to be a prefix of the ledger the four validators
end on. The catch-up's consistency proofs said so to the victim; here the
same statement is recomputed from the transactions themselves, with
hashlib alone: the RFC 6962 root over the disk's m leaves equals the root
over the first m leaves of the final sequence, and the root over all of
the final sequence is the one the validators report. A transaction altered
in the victim's log after it was hashed keeps every root the program
holds (its hash store is not recomputed at a restart) and shows only
here. Imports nothing of plenum_tpu."""
from __future__ import annotations

from benchmarks import reference


def contiguous_prefix(on_disk: dict) -> int:
    """{seqNo: txn} -> m, the length of the run 1..m the disk holds."""
    m = 0
    while m + 1 in on_disk:
        m += 1
    return m


def prefix_check(on_disk: dict, final_txns: list) -> dict:
    """on_disk: {seqNo: txn} of the victim's log at the kill; final_txns:
    the agreed ledger's transactions in order (index 0 is seqNo 1).
    -> what was compared, each number for a `compared` line."""
    m = contiguous_prefix(on_disk)
    beyond = sorted(s for s in on_disk if s > m)
    differing = [s for s in range(1, min(m, len(final_txns)) + 1)
                 if on_disk[s] != final_txns[s - 1]]
    disk_root = reference.merkle_root(
        reference.leaf_bytes(on_disk[s]) for s in range(1, m + 1))
    leaves = [reference.leaf_bytes(t) for t in final_txns]
    prefix_root = reference.merkle_root(leaves[:m])
    return {"disk_txns": m, "final_txns": len(final_txns),
            "disk_txns_past_a_hole": len(beyond),
            "disk_longer_than_final": max(0, m - len(final_txns)),
            "differing": differing,
            "disk_root": disk_root.hex(),
            "final_prefix_root": prefix_root.hex(),
            "final_root": reference.merkle_root(leaves).hex()}
