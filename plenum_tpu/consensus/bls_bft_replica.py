"""BLS wiring into the 3PC flow: sign state roots at COMMIT, aggregate at
order time, embed the previous batch's multi-sig into the next PRE-PREPARE.

Reference behavior: plenum/bls/bls_bft_replica_plenum.py:21 —
update_pre_prepare :80 / validate_pre_prepare :43 / update_commit :99
(_sign_state :227) / validate_commit :55 / process_commit :144 /
process_order :154 (_calculate_all_multi_sigs :261) — and plenum/bls/
bls_store.py (root-hash → multi-sig KV used by state-proof reads).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, NamedTuple, Optional

from plenum_tpu.common.metrics import MetricsName
from plenum_tpu.common.node_messages import Commit, PrePrepare
from plenum_tpu.common.quorums import Quorums
from plenum_tpu.common.serialization import json_dumps, json_loads
from plenum_tpu.crypto.bls import (BatchCheck, BlsCryptoSigner,
                                   BlsCryptoVerifier)
from plenum_tpu.crypto.bn254 import PAIRING_STATS
from plenum_tpu.crypto.multi_signature import (MultiSignature,
                                               MultiSignatureValue)
from plenum_tpu.storage.kv_store import KeyValueStorage


class BlsKeyRegister:
    """node name → BLS verkey, sourced from the pool ledger NODE txns
    (ref plenum/bls/bls_key_register_pool_manager.py). Injectable for tests."""

    def __init__(self, keys: Optional[dict[str, str]] = None):
        self._keys: dict[str, str] = dict(keys or {})

    def get_key_by_name(self, node_name: str) -> Optional[str]:
        return self._keys.get(node_name)

    def set_key(self, node_name: str, verkey: Optional[str]) -> None:
        if verkey is None:
            self._keys.pop(node_name, None)
        else:
            self._keys[node_name] = verkey

    def known_nodes(self) -> list[str]:
        return list(self._keys)


class BlsStore:
    """Persistent root-hash → MultiSignature map consulted by state-proof
    reads (ref plenum/bls/bls_store.py)."""

    def __init__(self, kv: KeyValueStorage):
        self._kv = kv

    @property
    def kv(self) -> KeyValueStorage:
        return self._kv

    def put(self, multi_sig: MultiSignature) -> None:
        self._kv.put(multi_sig.value.state_root_hash.encode(),
                     json_dumps(multi_sig.to_list()).encode())

    def get(self, state_root_hash: str) -> Optional[MultiSignature]:
        data = self._kv.try_get(state_root_hash.encode())
        if data is None:
            return None
        return MultiSignature.from_list(json_loads(data))


class _Submitted(NamedTuple):
    """An order-time check begun and not landed yet: what the landing
    needs of the moment it was submitted in."""
    key: tuple                    # (view_no, pp_seq_no)
    pre_prepare: PrePrepare
    quorums: Quorums
    names: list                   # signers checked, sorted: the items' order
    sigs: dict                    # name -> signature
    check: BatchCheck
    pairings: int                 # Miller loops the submit itself counted
    late: bool                    # asked for by a late COMMIT, not by _order


class BlsBftReplica:
    PPR_NO_BLS_MULTISIG = 0      # benign: previous batch had no quorum yet
    PPR_BLS_MULTISIG_WRONG = 1
    CM_BLS_SIG_WRONG = 2

    def __init__(self,
                 node_name: str,
                 bls_signer: Optional[BlsCryptoSigner],
                 bls_verifier: BlsCryptoVerifier,
                 key_register: BlsKeyRegister,
                 bls_store: Optional[BlsStore] = None,
                 quorums: Optional[Quorums] = None,
                 node_reg_at: Optional[Callable[[str], Optional[list]]] = None,
                 key_at: Optional[Callable[[str, str],
                                           Optional[str]]] = None):
        self._node_name = node_name
        self._signer = bls_signer
        self._verifier = bls_verifier
        self._register = key_register
        self._store = bls_store
        self._quorums = quorums or Quorums(4)
        # pool-state-root -> node registry at that root (audit-ledger
        # lookup, wired by the node): a multi-sig is judged by the quorum
        # rules of the pool size it was CREATED under, not today's
        self._node_reg_at = node_reg_at
        # (name, pool_root_hex) -> BLS verkey at that pool state (historic
        # MPT read): after a key ROTATION the embedded sig from just before
        # the rotation batch verifies only against the OLD key
        self._key_at = key_at
        # (view_no, pp_seq_no) -> {node_name: sig}
        self._sigs: dict[tuple[int, int], dict[str, str]] = {}
        # state_root -> MultiSignature for recently ordered batches
        self._recent_multi_sigs: dict[str, MultiSignature] = {}
        # set by the node: called with the sender of a bad COMMIT signature
        # caught by the order-time per-signature fallback
        self.report_bad_signature: Optional[Callable[[str], None]] = None
        # set by the node: every freshly aggregated multi-sig (including
        # late pending-order retries) is announced so the read plane can
        # advance its signed-root anchor
        self.on_multi_sig: Optional[Callable[[MultiSignature], None]] = None
        # optional MetricsCollector (master instance only): commit-path
        # stage timer + the pairings-per-batch counter the batched-BLS
        # acceptance is judged by
        self.metrics = None
        # multi-sigs we aggregated or verified ourselves -> the highest
        # pp_seq_no we met each at: in steady state the primary embeds
        # exactly this into the next PRE-PREPARE, so validate_pre_prepare
        # can skip the pairing; and a view change re-sends every
        # PRE-PREPARE since the last stable checkpoint with the
        # multi-sig it carried, so the memory lasts as long as they do (gc)
        self._verified_ms_keys: dict[tuple, int] = {}
        # this node's COMMIT signatures since the last stable checkpoint,
        # by signed value (which holds no view number, so a batch
        # re-certified in a later view is the same value)
        # -> (pp_seq_no, signature), dropped by gc with the 3PC log
        self._own_sigs: dict[bytes, tuple[int, str]] = {}
        # ordered batches whose multi-sig fell short of quorum, retried as
        # late COMMITs arrive; and senders whose sig already failed for a key
        self._pending_order: dict[tuple[int, int], PrePrepare] = {}
        self._known_bad: dict[tuple[int, int], set[str]] = {}
        # quorum-complete aggregates that a LATE honest sig may still
        # upgrade: key -> (pre_prepare, participants). Without this a
        # node on a slow WAN link whose COMMIT always lands after the
        # n-f quorum is permanently absent from every multi-sig this
        # node emits (and a just-re-keyed node never visibly rejoins)
        self._aggregated: dict[tuple[int, int],
                               tuple[PrePrepare, tuple]] = {}
        # order-time checks submitted and not landed, in submit order
        # (`submit_order` / `land`): the pairing work of each runs on the
        # native library's worker thread, beside this node's loop
        self._submitted: deque[_Submitted] = deque()
        # what this replica's checks did, cumulative (VALIDATOR_INFO
        # `bls`): `offloaded` went to the worker, `inline` were settled
        # at the submit (verdict cache, malformed input, no native
        # library); `join_wait` the seconds this thread blocked in the
        # landings, `verify` the checks' own seconds, `verify_late`
        # those of them that a late COMMIT asked for (one fresh signature
        # where the quorum's check has three: the two modes of
        # `commit_path.bls_verify_time`). `ppr_multi_sig`: what became of
        # the multi-signatures the PRE-PREPAREs this node validated
        # carried: `known` were answered from `_verified_ms_keys` (this
        # node aggregated or checked the same one before), `paired` went
        # to the verifier (`paired_s` its seconds, a served verifier's
        # round trip included), and `joined_s` the seconds
        # `multi_sig_holds` first blocked landing this node's own check
        # of the same root
        self.stats = {"offloaded": 0, "inline": 0,
                      "join_wait": {"count": 0, "sum_s": 0.0},
                      "verify": {"count": 0, "sum_s": 0.0},
                      "verify_late": {"count": 0, "sum_s": 0.0},
                      "ppr_multi_sig": {"known": 0, "paired": 0,
                                        "paired_s": 0.0, "joined_s": 0.0}}

    def set_quorums(self, quorums: Quorums) -> None:
        self._quorums = quorums

    # --- signed payload ---------------------------------------------------

    @staticmethod
    def _signed_value(pre_prepare: PrePrepare) -> MultiSignatureValue:
        return MultiSignatureValue(
            ledger_id=pre_prepare.ledger_id,
            state_root_hash=pre_prepare.state_root,
            pool_state_root_hash=pre_prepare.pool_state_root,
            txn_root_hash=pre_prepare.txn_root,
            timestamp=pre_prepare.pp_time)

    # --- PRE-PREPARE ------------------------------------------------------

    def update_pre_prepare(self, params: dict, state_root: str) -> dict:
        """Attach the previous batch's aggregated multi-sig (by state root)."""
        self.land_all()
        ms = self._recent_multi_sigs.get(state_root)
        if ms is not None:
            params["bls_multi_sig"] = tuple(ms.to_list())
        return params

    def validate_pre_prepare(self, pre_prepare: PrePrepare, sender: str) -> Optional[int]:
        if pre_prepare.bls_multi_sig is None:
            return None
        try:
            ms = MultiSignature.from_list(list(pre_prepare.bls_multi_sig))
        except (ValueError, TypeError, IndexError, KeyError):
            return self.PPR_BLS_MULTISIG_WRONG
        return None if self.multi_sig_holds(
            ms, pre_prepare.pp_seq_no, self.stats["ppr_multi_sig"]) \
            else self.PPR_BLS_MULTISIG_WRONG

    def multi_sig_holds(self, ms: MultiSignature, seq: int = 0,
                        tally: Optional[dict] = None) -> bool:
        """Whether `ms` is a quorum multi-signature over its value, by the
        keys and the quorum of the pool state it cites. `seq`: the
        pp_seq_no of the PRE-PREPARE that carries it; the verdict is
        remembered until a checkpoint at or past it is stable. `tally`:
        the `ppr_multi_sig` record of `stats`, where the caller's
        multi-signatures are counted (a PRE-PREPARE's are)."""
        # Participants must be DISTINCT registered validators: aggregation is
        # plain point addition, so one colluding node's signature repeated
        # n-f times would otherwise verify as a quorum multi-sig (rogue
        # self-aggregation).
        if len(set(ms.participants)) != len(ms.participants):
            return False
        # a check of this node's own over the same root, still with the
        # worker, may be about to aggregate this very multi-signature
        for sub in self._submitted:
            if sub.pre_prepare.state_root == ms.value.state_root_hash:
                t0 = time.perf_counter()
                self.land(sub.key)
                if tally is not None:
                    tally["joined_s"] += time.perf_counter() - t0
                break
        # A multi-sig we aggregated (or fully verified) OURSELVES passed the
        # quorum rules in force when it was created. This shortcut must come
        # BEFORE the current-quorum check: the first PRE-PREPARE after a pool
        # membership change legitimately embeds the previous batch's sig,
        # whose participant count satisfies the OLD n - f, not the new one —
        # re-judging it with the new quorums would mark every honest primary
        # suspicious and storm view changes on every pool growth.
        ms_key = self._ms_key(ms)
        if ms_key in self._verified_ms_keys:
            self._remember_verified(ms_key, seq)
            if tally is not None:
                tally["known"] += 1
            return True
        # keys AND quorum AS OF the sig's cited pool state — the same
        # epoch resolution process_order aggregates under, so an honest
        # aggregate passes here BY CONSTRUCTION (each node's aggregate can
        # pick a different participant subset, so the self-verified
        # shortcut alone cannot cover membership changes)
        key_of, reg, quorums = self._epoch_of(ms.value.pool_state_root_hash)
        vk_of = {n: key_of(n) for n in ms.participants}
        if any(v is None for v in vk_of.values()):
            return False
        if reg is not None and not set(ms.participants) <= set(reg):
            return False
        if not quorums.bls_signatures.is_reached(len(ms.participants)):
            return False
        t0 = time.perf_counter()
        ok = self._verifier.verify_multi_sig(ms.signature,
                                             ms.value.as_single_value(),
                                             [vk_of[n] for n in
                                              ms.participants])
        if tally is not None:
            tally["paired"] += 1
            tally["paired_s"] += time.perf_counter() - t0
        self._drop_stale_points(vk_of)
        if ok:
            self._remember_verified(ms_key, seq)
        return ok

    def adopt_multi_sig(self, ms: MultiSignature) -> bool:
        """A multi-signature a peer sent for a root this node reached by
        catch-up (it saw no COMMITs for that batch): checked like one a
        PRE-PREPARE carries, then kept and announced like one aggregated
        here."""
        if not self.multi_sig_holds(ms):
            return False
        if self._store is not None:
            self._store.put(ms)
        if self.on_multi_sig is not None:
            self.on_multi_sig(ms)
        return True

    # --- COMMIT -----------------------------------------------------------

    def update_commit(self, params: dict,
                      pre_prepare: PrePrepare) -> tuple[dict, bool]:
        """-> (params with this node's signature, whether it is the one
        kept from an earlier COMMIT over the same value). The signature is
        sk * H(value): a fresh one would be the same bytes."""
        if self._signer is None:
            return params, False
        value = self._signed_value(pre_prepare).as_single_value()
        reused = value in self._own_sigs
        if not reused:
            self._own_sigs[value] = (pre_prepare.pp_seq_no,
                                     self._signer.sign(value))
        params["bls_sig"] = self._own_sigs[value][1]
        return params, reused

    def validate_commit(self, commit: Commit, sender_node: str,
                        pre_prepare: PrePrepare) -> Optional[int]:
        """DEFERRED verification: only the cheap structural check happens per
        COMMIT. The ~74x more expensive pairing runs ONCE per batch when the
        commit quorum forms, as a random-linear-combination batch check with
        per-signature fallback to evict liars (process_order) — per-commit
        pairings were the dominant term in pool TPS (one pairing per peer
        COMMIT per batch per node)."""
        if commit.bls_sig is None:
            return None
        if not self._verifier.is_wellformed_sig(commit.bls_sig):
            return self.CM_BLS_SIG_WRONG
        return None

    def process_commit(self, commit: Commit, sender_node: str) -> None:
        if commit.bls_sig is None:
            return
        key = (commit.view_no, commit.pp_seq_no)
        self._sigs.setdefault(key, {})[sender_node] = commit.bls_sig
        # the batch's check is still with the worker: whatever it finds,
        # a signer it did not see asks again, behind it (the signatures it
        # is checking are not checked twice: crypto/bls.py _IN_FLIGHT)
        for sub in reversed(self._submitted):
            if sub.key == key:
                if sender_node not in sub.names:
                    self._submit(key, sub.pre_prepare, late=True)
                return
        # A batch can order before every honest COMMIT arrives; if its
        # multi-sig aggregation fell short of quorum (e.g. one bad signature
        # evicted by the bisection), late honest sigs must retry it — or a
        # single Byzantine racer could suppress multi-sigs forever.
        pending = self._pending_order.get(key)
        if pending is not None:
            self._submit(key, pending, late=True)
            return
        # late sig for an already-aggregated batch: re-aggregate so the
        # sender joins the multi-sig (verdicts of the existing members
        # ride the process-wide cache — the upgrade prices one combined
        # check of the new sig, not n pairings)
        agg = self._aggregated.get(key)
        if agg is not None and sender_node not in agg[1]:
            self._submit(key, agg[0], late=True)

    # --- order ------------------------------------------------------------

    def process_order(self, key: tuple[int, int],
                      pre_prepare: PrePrepare) -> Optional[MultiSignature]:
        """Submit and land at once: for a caller that needs the
        multi-signature now."""
        self.submit_order(key, pre_prepare)
        return self.land(key)

    def submit_order(self, key: tuple[int, int],
                     pre_prepare: PrePrepare) -> None:
        """The batch is ordered: begin the check of its COMMIT
        signatures. Whatever follows from the verdicts happens in the
        landing, at the first point that reads them (`update_pre_prepare`,
        the close of the group commit, `gc`, ...); in between the pairing
        work runs beside this thread."""
        self._submit(key, pre_prepare, late=False)

    def _submit(self, key: tuple[int, int], pre_prepare: PrePrepare,
                late: bool) -> None:
        # Aggregate under the keys and quorum of the EPOCH the sig value
        # cites (the pre-prepare's pool state root), not the node's current
        # register: around a rotation or demotion the two differ, and every
        # validator judging the embedded aggregate re-derives the CITED
        # epoch (validate_pre_prepare) — an aggregate judged by current
        # membership would fail on every honest peer and storm view changes
        # (churn-soak waves: 3-participant sigs citing a 5-node root, and
        # stale-register aggregates spanning a rotation window).
        key_of, _reg, quorums = self._epoch_of(pre_prepare.pool_state_root)
        # one historic-epoch key resolution per signer per call: _key_at
        # is a historic pool-state read, and this path re-runs on every
        # late COMMIT
        vk_of = {n: key_of(n) for n in self._sigs.get(key, {})}
        sigs = {n: s for n, s in self._sigs.get(key, {}).items()
                if vk_of[n] is not None
                and n not in self._known_bad.get(key, set())}
        if not quorums.bls_signatures.is_reached(len(sigs)):
            self._pending_order[key] = pre_prepare      # retry on late sigs
            return
        # Validate the whole COMMIT set with ONE random-linear-combination
        # pairing check (crypto.bls.BlsCryptoVerifier.batch_verify_begin):
        # every signer signs the same ordered-batch value, so the combined
        # check costs 2 pairings regardless of pool size — amortized O(1)
        # vs the Θ(n) independent 2-pairing checks of per-Commit
        # verification. On failure the verifier falls back to
        # per-signature checks, which name the culprit(s) exactly (no
        # subset bisection: plain-aggregation subsets can be satisfied by
        # error-cancelling signature pairs, the RLC cannot).
        value = self._signed_value(pre_prepare).as_single_value()
        names = sorted(sigs)
        pairings_before = PAIRING_STATS["pairings"]
        check = self._verifier.batch_verify_begin(
            [(sigs[n], value, vk_of[n]) for n in names])
        self._drop_stale_points(vk_of)
        self.stats["offloaded" if check.offloaded else "inline"] += 1
        self._submitted.append(_Submitted(
            key, pre_prepare, quorums, names, sigs, check,
            PAIRING_STATS["pairings"] - pairings_before, late))

    def land_all(self) -> None:
        """Land every check submitted, in submit order; blocks while the
        worker still computes one of them."""
        while self._submitted:
            self._land_first()

    def land_ordered(self, key: tuple[int, int]) -> None:
        """Land through the check that `submit_order` began for `key`, and
        leave what late COMMITs asked for behind it: the multi-signature
        of that batch and of every batch ordered before it is in the
        store when this returns (the group commit closes on it, a REPLY
        follows), and no REPLY waits for an upgrade by a fourth signer."""
        n = next((i + 1 for i, sub in enumerate(self._submitted)
                  if sub.key == key and not sub.late), 0)
        for _ in range(n):
            self._land_first()

    def land_done(self) -> None:
        """Land what the worker has finished, in submit order, and stop
        at the first check it has not: never blocks."""
        while self._submitted and self._verifier.batch_verify_ready(
                self._submitted[0].check):
            self._land_first()

    def land(self, key: tuple[int, int]) -> Optional[MultiSignature]:
        """Land through the newest check submitted for `key` (what follows
        from verdicts happens in submit order, so every earlier check
        lands first). -> the multi-signature that check aggregated, if it
        did."""
        ms = None
        while any(sub.key == key for sub in self._submitted):
            landed, ms = self._land_first()
            if landed != key:
                ms = None
        return ms

    def _land_first(self) -> tuple[tuple[int, int], Optional[MultiSignature]]:
        sub = self._submitted.popleft()
        key = sub.key
        pre_prepare, quorums = sub.pre_prepare, sub.quorums
        pairings_before = PAIRING_STATS["pairings"]
        t0 = time.perf_counter()
        oks = self._verifier.batch_verify_end(sub.check)
        waited = time.perf_counter() - t0
        good = {n: sub.sigs[n] for n, ok in zip(sub.names, oks) if ok}
        bad = [n for n, ok in zip(sub.names, oks) if not ok]
        self._note(self.stats["verify"], sub.check.seconds)
        if sub.late:
            self._note(self.stats["verify_late"], sub.check.seconds)
        self._note(self.stats["join_wait"], waited)
        if self.metrics is not None:
            self.metrics.add_event(MetricsName.COMMIT_BLS_VERIFY_TIME,
                                   sub.check.seconds)
            self.metrics.add_event(MetricsName.COMMIT_BLS_JOIN_WAIT, waited)
            self.metrics.add_event(
                MetricsName.BLS_PAIRINGS_PER_BATCH, sub.pairings
                + PAIRING_STATS["pairings"] - pairings_before)
        for sender in bad:
            known = self._known_bad.setdefault(key, set())
            if sender in known:
                continue        # a check begun before the one that named it
            known.add(sender)
            if self.report_bad_signature is not None:
                self.report_bad_signature(sender)
        if not quorums.bls_signatures.is_reached(len(good)):
            self._pending_order[key] = pre_prepare      # retry on late sigs
            return key, None
        self._pending_order.pop(key, None)
        participants = tuple(sorted(good))
        prev = self._aggregated.get(key)
        if prev is not None and set(participants) <= set(prev[1]):
            return key, None    # no new honest signer: keep the aggregate
        agg = self._verifier.create_multi_sig([good[n] for n in participants])
        ms = MultiSignature(signature=agg, participants=participants,
                            value=self._signed_value(pre_prepare))
        self._remember_verified(self._ms_key(ms), key[1])
        self._recent_multi_sigs[pre_prepare.state_root] = ms
        if len(self._recent_multi_sigs) > 10:
            oldest = next(iter(self._recent_multi_sigs))
            del self._recent_multi_sigs[oldest]
        self._aggregated.pop(key, None)     # re-insert newest-last
        self._aggregated[key] = (pre_prepare, participants)
        while len(self._aggregated) > 10:
            del self._aggregated[next(iter(self._aggregated))]
        if self._store is not None:
            self._store.put(ms)
        if self.on_multi_sig is not None:
            self.on_multi_sig(ms)
        return key, ms

    @staticmethod
    def _note(record: dict, seconds: float) -> None:
        record["count"] += 1
        record["sum_s"] += seconds

    @property
    def depth(self) -> int:
        """Checks submitted and not landed."""
        return len(self._submitted)

    def tally(self) -> dict:
        """`stats` as flat numbers whose differences say what this replica
        did over an interval (a view change's last phase:
        OrderingService.vc_episode `bls`)."""
        st, ppr = self.stats, self.stats["ppr_multi_sig"]
        return {"submitted": st["offloaded"] + st["inline"],
                "offloaded": st["offloaded"], "inline": st["inline"],
                "landings": st["join_wait"]["count"],
                "join_wait_ms": st["join_wait"]["sum_s"] * 1e3,
                "verify_ms": st["verify"]["sum_s"] * 1e3,
                "ppr_known": ppr["known"], "ppr_paired": ppr["paired"],
                "ppr_paired_ms": ppr["paired_s"] * 1e3,
                "ppr_joined_ms": ppr["joined_s"] * 1e3}

    def _epoch_of(self, pool_root: str):
        """-> (key_of, reg, quorums) AS OF `pool_root` — the epoch a
        multi-sig value cites. Unresolvable history falls back to the
        current register/quorums. Aggregation (process_order) and
        validation (validate_pre_prepare) MUST share this resolution:
        any divergence makes honest aggregates look forged."""
        quorums = self._quorums
        reg = None
        if self._node_reg_at is not None:
            reg = self._node_reg_at(pool_root) or None
            if reg:
                quorums = Quorums(len(reg))

        def key_of(n: str) -> Optional[str]:
            vk = self._key_at(n, pool_root) \
                if self._key_at is not None else None
            return vk or self._register.get_key_by_name(n)
        return key_of, reg, quorums

    def _drop_stale_points(self, vk_of: dict[str, Optional[str]]) -> None:
        """A historic-epoch verify (a batch citing a pre-rotation pool
        root) legitimately decodes the rotated-OUT key — but it must not
        stay warm in the key table past the check, or the eviction
        contract node._on_pool_changed enforces is undone by the next
        in-flight batch."""
        for n, vk in vk_of.items():
            if vk is not None and vk != self._register.get_key_by_name(n):
                self._verifier.evict_key(vk)

    @staticmethod
    def _ms_key(ms: MultiSignature) -> tuple:
        return (ms.signature, tuple(ms.participants),
                ms.value.as_single_value())

    def _remember_verified(self, ms_key: tuple, seq: int) -> None:
        self._verified_ms_keys[ms_key] = max(
            seq, self._verified_ms_keys.get(ms_key, 0))

    def gc(self, stable_3pc: tuple[int, int]) -> None:
        self.land_all()
        seq = stable_3pc[1]
        # the multi-sig of the checkpoint's own last batch stays: the
        # PRE-PREPARE after it carries it
        self._verified_ms_keys = {k: v for k, v in
                                  self._verified_ms_keys.items() if v >= seq}
        self._own_sigs = {k: v for k, v in self._own_sigs.items()
                          if v[0] > seq}
        self._sigs = {k: v for k, v in self._sigs.items() if k > stable_3pc}
        self._pending_order = {k: v for k, v in self._pending_order.items()
                               if k > stable_3pc}
        self._known_bad = {k: v for k, v in self._known_bad.items()
                           if k > stable_3pc}
        self._aggregated = {k: v for k, v in self._aggregated.items()
                            if k > stable_3pc}
