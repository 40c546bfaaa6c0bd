"""Node bootstrap: storages, ledgers, states, handlers, managers, BLS, authN.

Reference behavior: plenum/server/node_bootstrap.py:17 + ledgers_bootstrap.py —
build the 4 base ledgers in catchup order (audit, pool, config, domain,
node.py:142), a state trie per non-audit ledger, register request + batch
handlers, wire BLS, and replay committed txns into state so a restarted (or
genesis-seeded) node starts from consistent roots.
"""
from __future__ import annotations

import os
import time
from typing import NamedTuple, Optional, Sequence

from plenum_tpu.common.serialization import pack, unpack
from plenum_tpu.common.node_messages import (AUDIT_LEDGER_ID,
                                             CONFIG_LEDGER_ID,
                                             DOMAIN_LEDGER_ID, POOL_LEDGER_ID)
from plenum_tpu.consensus.bls_bft_replica import (BlsBftReplica, BlsKeyRegister,
                                                  BlsStore)
from plenum_tpu.crypto.bls import BlsCryptoSigner, BlsCryptoVerifier
from plenum_tpu.crypto.ed25519 import make_verifier
from plenum_tpu.execution import (DatabaseManager, LedgerBatchExecutor,
                                  ReadRequestManager, WriteRequestManager)
from plenum_tpu.execution.database_manager import (BLS_STORE_LABEL,
                                                   NODE_STATUS_DB_LABEL,
                                                   SEQ_NO_DB_LABEL,
                                                   TS_STORE_LABEL)
from plenum_tpu.execution.handlers import (GetFrozenLedgersHandler,
                                           GetNymHandler,
                                           GetTxnAuthorAgreementAmlHandler,
                                           GetTxnAuthorAgreementHandler,
                                           GetTxnHandler, LedgersFreezeHandler,
                                           NodeHandler, NymHandler,
                                           TxnAuthorAgreementAmlHandler,
                                           TxnAuthorAgreementDisableHandler,
                                           TxnAuthorAgreementHandler)
from plenum_tpu.execution.txn import NODE, NYM
from plenum_tpu.execution import txn as txn_lib
from plenum_tpu.storage.state_ts_store import StateTsStore
from plenum_tpu.ledger.compact_merkle_tree import CompactMerkleTree
from plenum_tpu.ledger.hash_store import HashStore
from plenum_tpu.ledger.ledger import Ledger
from plenum_tpu.ledger.tree_hasher import make_tree_hasher
from plenum_tpu.node.client_authn import CoreAuthNr, ReqAuthenticator
from plenum_tpu.node.pool_manager import TxnPoolManager
from plenum_tpu.storage.kv_file import KvFile
from plenum_tpu.storage.kv_memory import KvMemory
from plenum_tpu.state.trie import BLANK_ROOT


# A group commit flushes every store before the next one opens, so one
# validator's stores differ by at most one scope, and a scope holds at most
# Config.GROUP_COMMIT_MAX_BATCHES (32) batches: how far back a restart looks
# for the batch a lagging named store stopped at.
RECONCILE_BATCHES = 64


def last_whole_batch(db: DatabaseManager) -> int:
    """The newest audit txn whose batch every ledger of this validator
    holds in full (0: none). A crash in mid-commit leaves the stores at
    different batches: each is flushed on its own, and the native engine
    can leave a prefix of a scope."""
    audit = db.get_ledger(AUDIT_LEDGER_ID)
    keep = audit.size
    while keep > 0:
        sizes = txn_lib.txn_data(audit.get_by_seq_no(keep))["ledgerSize"]
        if all(db.get_ledger(int(lid)).size >= size
               for lid, size in sizes.items()):
            break
        keep -= 1
    return keep


def roll_back_to_audit(db: DatabaseManager, wm: WriteRequestManager,
                       keep: int, floor: dict) -> dict:
    """Bring every store of this validator to the batch of audit txn
    `keep`: ledgers and the audit ledger cut back to it, each state at the
    root it records (moved there, or replayed from the ledger where the
    trie lacks it), the seq-no and timestamp stores holding its txns and
    none past them. `floor`: ledger id -> size below which nothing is cut
    (the genesis), used when no audit txn is kept. Idempotent.
    -> what it did, for the start line."""
    audit = db.get_ledger(AUDIT_LEDGER_ID)
    report = {"audit_txns_dropped": audit.size - keep, "txns_dropped": {},
              "state": {}}
    kept = txn_lib.txn_data(audit.get_by_seq_no(keep)) if keep else {}
    sizes = {int(lid): size
             for lid, size in kept.get("ledgerSize", floor).items()}
    seq_no_db = db.get_store(SEQ_NO_DB_LABEL)
    for lid, ledger in db.ledgers():
        want = sizes.get(lid)
        if lid == AUDIT_LEDGER_ID or want is None or want >= ledger.size:
            continue
        report["txns_dropped"][lid] = len(ledger.truncate(want))
    audit.truncate(keep)
    if seq_no_db is not None and (report["audit_txns_dropped"]
                                  or report["txns_dropped"]):
        # entries of txns that are gone (cut above, or never flushed to
        # their log): a resend would be answered from a seqNo that is
        # not theirs
        gone = []
        for key, raw in seq_no_db.iterator():
            lid, seq_no = unpack(raw)[:2]
            if seq_no > db.get_ledger(lid).size:
                gone.append(key)
        for key in gone:
            seq_no_db.remove(key)

    for lid, ledger in db.ledgers():
        state = db.get_state(lid)
        if state is None:
            continue
        root_hex = kept.get("stateRoot", {}).get(str(lid))
        if not hasattr(state, "has_root"):
            continue        # a backend with no addressable roots: as found
        if root_hex is None:
            # no batch kept: the state is the genesis's, rebuilt below
            # (_replay_genesis_state) if txns were cut from under it
            if report["txns_dropped"].get(lid):
                state.commit(BLANK_ROOT)
                state.revert_to_head(BLANK_ROOT)
                report["state"][lid] = "reset"
            continue
        wanted = bytes.fromhex(root_hex)
        if state.committed_head_hash == wanted:
            continue
        if state.has_root(wanted):
            state.commit(wanted)
            state.revert_to_head(wanted)
            report["state"][lid] = "moved"
            continue
        # the trie lacks the batch: replay the ledger from the newest
        # batch it does hold
        start, at = 0, BLANK_ROOT
        for seq in range(keep, max(0, keep - RECONCILE_BATCHES), -1):
            data = txn_lib.txn_data(audit.get_by_seq_no(seq))
            root = bytes.fromhex(data["stateRoot"][str(lid)])
            if state.has_root(root):
                start, at = data["ledgerSize"][str(lid)], root
                break
        state.commit(at)
        state.revert_to_head(at)
        for seq in range(start + 1, ledger.size + 1):
            wm.apply_committed_txn(lid, ledger.get_by_seq_no(seq),
                                   committed=False)
        if state.head_hash != wanted:
            raise RuntimeError(
                f"ledger {lid}: its txns replay to state root "
                f"{state.head_hash.hex()}, the audit ledger records "
                f"{root_hex} at batch {keep}")
        state.commit(wanted)
        report["state"][lid] = {"replayed_txns": ledger.size - start}

    # the named stores behind the ledgers: rows of the last scope
    ts_store = db.get_store(TS_STORE_LABEL)
    if keep and ts_store is not None:
        at = txn_lib.txn_time(audit.get_by_seq_no(keep))
        late = [k for k in ts_store.kv.iterator(include_value=False)
                if int.from_bytes(k[2:], "big") > at]
        for k in late:
            ts_store.kv.remove(k)
    first = max(1, keep - RECONCILE_BATCHES + 1)
    prev = txn_lib.txn_data(audit.get_by_seq_no(first - 1))["ledgerSize"] \
        if first > 1 else {str(lid): size for lid, size in floor.items()}
    rows, roots_at = 0, {}
    for seq in range(first, keep + 1):
        txn = audit.get_by_seq_no(seq)
        data = txn_lib.txn_data(txn)
        lid = data["ledgerId"]
        root_hex = data.get("stateRoot", {}).get(str(lid))
        if root_hex is not None:
            # the store keys by whole seconds: the last batch of one wins
            roots_at[(lid, txn_lib.txn_time(txn))] = bytes.fromhex(root_hex)
        ledger = db.get_ledger(lid)
        for n in range(prev.get(str(lid), 0) + 1,
                       data["ledgerSize"][str(lid)] + 1):
            done = ledger.get_by_seq_no(n)
            digest = txn_lib.txn_payload_digest(done)
            if seq_no_db is not None and digest \
                    and not seq_no_db.has_key(digest.encode()):
                seq_no_db.put(digest.encode(), pack(
                    (lid, n, txn_lib.txn_time(done))))
                rows += 1
        prev = data["ledgerSize"]
    for (lid, at), root in roots_at.items():
        if ts_store is not None and ts_store.get(lid, at) != root:
            ts_store.set(lid, at, root)
            rows += 1
    report["named_store_rows_restored"] = rows
    return report


class NodeComponents(NamedTuple):
    db: DatabaseManager
    write_manager: WriteRequestManager
    read_manager: ReadRequestManager
    executor: LedgerBatchExecutor
    authenticator: ReqAuthenticator
    pool_manager: TxnPoolManager
    nym_handler: NymHandler
    node_handler: NodeHandler
    bls_signer: Optional[BlsCryptoSigner]
    bls_register: BlsKeyRegister
    bls_store: BlsStore
    plugins: list = []          # effective plugin objects (init'd by Node)
    action_manager: object = None
    # fused crypto pipeline (parallel/pipeline.py) the node's crypto
    # seams ride when constructed with one; co-hosted nodes share it
    pipeline: object = None
    # what a start on durable stores found and reconciled (None on memory)
    recovery: Optional[dict] = None


class NodeBootstrap:
    """Builds everything below the Node orchestrator."""

    def __init__(self, name: str,
                 genesis_txns: Optional[dict[int, Sequence[dict]]] = None,
                 data_dir: Optional[str] = None,
                 crypto_backend: str = "cpu",
                 bls_seed: Optional[bytes] = None,
                 verifier_min_batch: int = 128,
                 storage_backend: str = "native",
                 plugins=None,
                 verifier=None,
                 pipeline=None,
                 pipeline_lane: Optional[int] = None,
                 state_commitment: str = "mpt",
                 state_commitment_per_ledger: Optional[dict] = None,
                 verkle_width: Optional[int] = None):
        self.name = name
        self.genesis = genesis_txns or {}
        self.data_dir = data_dir
        self.crypto_backend = crypto_backend
        # durable stores: "native" = the C++ log-structured engine
        # (LevelDB/RocksDB slot), "file" = the pure-python append log
        self.storage_backend = storage_backend
        # extension handlers (ref plugin_loader.py); merged with the
        # globally-registered set at build time
        self.plugins = list(plugins or [])
        self.bls_seed = bls_seed or name.encode().ljust(32, b"\0")[:32]
        # one fixed device-program shape covering the receive quotas: novel
        # shapes recompile, which costs minutes per shape
        self.verifier_min_batch = verifier_min_batch
        # explicit verifier override: co-hosted nodes pass ONE shared
        # verifier (the ring's `verifier()` view, or the SPMD plane's
        # sharded verifier) so their dispatches share a device plane
        self.verifier = verifier
        # fused crypto pipeline (parallel/pipeline.py): when given, the
        # authenticator, every ledger's tree hasher, and the BLS batch
        # checks all stage into its shared ring (co-hosted nodes pass ONE
        # instance — that sharing IS the cross-node coalescing/dedup)
        self.pipeline = pipeline
        # multi-device ring placement pin: this node's submissions stage
        # into the named chip lane (sharded fabrics pin co-hosted
        # sub-pool shards to DISTINCT chips; None = ring-chosen lane)
        self.pipeline_lane = pipeline_lane
        # per-ledger state commitment scheme (state/commitment/): 'mpt'
        # default, 'verkle' for aggregated multi-key openings; the whole
        # pool must agree (the backend defines the signed root anchors)
        self.state_commitment = state_commitment
        self.state_commitment_per_ledger = \
            dict(state_commitment_per_ledger or {})
        self.verkle_width = verkle_width
        self.opened: dict[str, dict] = {}   # durable stores, by label

    # --- storage factories -------------------------------------------------

    def _kv(self, label: str):
        if self.data_dir is None:
            return KvMemory()
        t0 = time.perf_counter()
        kv = self._durable_kv(label)
        # for the start line: which engine holds the store, the rows its
        # log replayed into the index, the seconds that took
        self.opened[label] = {"engine": getattr(kv, "engine", "chunked"),
                              "rows": kv.size,
                              "open_s": round(time.perf_counter() - t0, 4)}
        return kv

    def _durable_kv(self, label: str):
        os.makedirs(self.data_dir, exist_ok=True)
        path = os.path.join(self.data_dir, label)
        has_native = os.path.exists(os.path.join(path, "kv.kvn"))
        has_file = os.path.exists(os.path.join(path, "kv.kvlog"))
        if self.storage_backend == "chunked" and not (has_native or has_file):
            # unbounded append logs split across sealed chunk files
            # (ref chunked_file_store.py); existing single-file/native
            # data keeps its on-disk format
            from plenum_tpu.storage.kv_chunked import KvChunked
            return KvChunked(path)
        if self.storage_backend == "native" or has_native:
            from plenum_tpu.storage.kv_native import (KvNative,
                                                      native_available)
            if native_available():
                if has_file and not has_native:
                    # existing KvFile data: honor the on-disk format rather
                    # than silently opening an empty native store
                    return KvFile(path)
                return KvNative(path)
            if has_native:
                # NEVER silently restart from genesis because the toolchain
                # went away: the durable data is in the native format
                raise RuntimeError(
                    f"{path} holds native-engine data but the native "
                    f"kvstore is unavailable (g++ build failed?)")
            import logging
            logging.getLogger(__name__).warning(
                "native kvstore unavailable; falling back to the "
                "pure-python file log for %s", path)
        return KvFile(path)

    def _ledger(self, ledger_id: int, label: str) -> Ledger:
        # crypto_backend routes to EVERY ledger's tree hasher — with "jax"
        # the batch appends/proof paths run on device (the north-star seam;
        # ref tree_hasher.py:4 + SURVEY.md §7 stage 2/3); with a pipeline,
        # hashing coalesces/dedups through its shared SHA lane instead
        hasher = (self.pipeline.tree_hasher() if self.pipeline is not None
                  else make_tree_hasher(self.crypto_backend))
        tree = CompactMerkleTree(
            hasher,
            hash_store=HashStore(self._kv(f"{label}_hashes")))
        return Ledger(tree, self._kv(f"{label}_log"),
                      genesis_txns=self.genesis.get(ledger_id, ()))

    # --- build -------------------------------------------------------------

    def _state(self, ledger_id: int, label: str):
        """Per-ledger state through the commitment seam: the configured
        scheme ('mpt' default; the Verkle backend additionally stages its
        batch commitment updates through the shared pipeline's commitment
        wave kind when one is wired)."""
        from plenum_tpu.state.commitment import (backend_for_ledger,
                                                 make_state)
        backend = backend_for_ledger(ledger_id, self.state_commitment,
                                     self.state_commitment_per_ledger)
        return make_state(backend, db=self._kv(label),
                          width=self.verkle_width, pipeline=self.pipeline)

    def build(self) -> NodeComponents:
        db = DatabaseManager()
        # the commit drain's fused wave seam (execution/write_manager.py
        # `_commit_wave`): same pipeline the states commit through
        db.pipeline = self.pipeline
        # catchup order: audit, pool, config, domain (ref node.py:142)
        db.register_ledger(AUDIT_LEDGER_ID, self._ledger(AUDIT_LEDGER_ID, "audit"))
        db.register_ledger(POOL_LEDGER_ID, self._ledger(POOL_LEDGER_ID, "pool"),
                           self._state(POOL_LEDGER_ID, "pool_state"))
        db.register_ledger(CONFIG_LEDGER_ID, self._ledger(CONFIG_LEDGER_ID, "config"),
                           self._state(CONFIG_LEDGER_ID, "config_state"))
        db.register_ledger(DOMAIN_LEDGER_ID, self._ledger(DOMAIN_LEDGER_ID, "domain"),
                           self._state(DOMAIN_LEDGER_ID, "domain_state"))
        db.register_store(TS_STORE_LABEL,
                          StateTsStore(self._kv("ts_store")))
        db.register_store(SEQ_NO_DB_LABEL, self._kv("seq_no_db"))
        db.register_store(NODE_STATUS_DB_LABEL, self._kv("node_status_db"))
        bls_store = BlsStore(self._kv("bls_store"))
        db.register_store(BLS_STORE_LABEL, bls_store)

        # handlers + managers
        write_manager = WriteRequestManager(db)
        nym = NymHandler(db)
        bls_verifier = BlsCryptoVerifier()
        node_handler = NodeHandler(db, nym, bls_verifier=bls_verifier)
        write_manager.register_handler(nym)
        write_manager.register_handler(node_handler)
        write_manager.register_handler(TxnAuthorAgreementHandler(db, nym))
        write_manager.register_handler(TxnAuthorAgreementAmlHandler(db, nym))
        write_manager.register_handler(TxnAuthorAgreementDisableHandler(db, nym))
        write_manager.register_handler(LedgersFreezeHandler(db, nym))
        from plenum_tpu.execution.handlers.attrib import (
            ATTRIB_STORE_LABEL, AttribHandler, GetAttrHandler)
        db.register_store(ATTRIB_STORE_LABEL, self._kv("attrib_db"))
        write_manager.register_handler(AttribHandler(db))
        read_manager = ReadRequestManager()
        read_manager.register_handler(GetAttrHandler(db))
        read_manager.register_handler(GetNymHandler(db))
        read_manager.register_handler(GetTxnHandler(db))
        read_manager.register_handler(GetTxnAuthorAgreementHandler(db))
        read_manager.register_handler(GetTxnAuthorAgreementAmlHandler(db))
        read_manager.register_handler(GetFrozenLedgersHandler(db))

        # action requests: privileged, node-local, no consensus
        # (ref action_request_manager.py; Node registers its own handlers)
        from plenum_tpu.execution.action_manager import ActionRequestManager
        action_manager = ActionRequestManager(get_role=nym.get_role)

        # plugins contribute extra txn types before genesis replay so
        # plugin txns can even appear in genesis (ref plugin_loader.py)
        from plenum_tpu.plugins import install_plugins
        self.effective_plugins = install_plugins(
            db, write_manager, read_manager, self.plugins)

        recovery = self._recover(db, write_manager)
        t0 = time.perf_counter()
        replayed = self._replay_genesis_state(db, nym, node_handler,
                                              write_manager)
        if recovery is not None:
            recovery["state_replayed_from_ledger"] = replayed
            recovery["seconds"]["replay_state"] = round(
                time.perf_counter() - t0, 3)

        # client authN over the Ed25519 provider seam (cpu | jax); with a
        # pipeline the batches stage into the shared ring instead of
        # dispatching alone
        if self.verifier is not None:
            authn_verifier = self.verifier
        elif self.pipeline is not None:
            authn_verifier = self.pipeline.verifier(
                lane=self.pipeline_lane)
        else:
            authn_verifier = make_verifier(
                self.crypto_backend, min_batch=self.verifier_min_batch)
        authnr = ReqAuthenticator()
        authnr.register_authenticator(CoreAuthNr(
            authn_verifier, get_verkey=nym.get_verkey))

        # BLS: signer from seed; registry fed from pool state
        bls_signer = BlsCryptoSigner(seed=self.bls_seed)
        bls_register = BlsKeyRegister()
        pool_manager = TxnPoolManager(node_handler)
        self._sync_bls_register(bls_register, pool_manager)

        executor = LedgerBatchExecutor(write_manager)
        return NodeComponents(db, write_manager, read_manager, executor,
                              authnr, pool_manager, nym, node_handler,
                              bls_signer, bls_register, bls_store,
                              self.effective_plugins, action_manager,
                              self.pipeline, recovery)

    def genesis_sizes(self) -> dict:
        return {lid: len(txns) for lid, txns in self.genesis.items() if txns}

    def _recover(self, db, wm) -> Optional[dict]:
        """On durable stores: bring this validator's stores to one batch
        (a first start finds nothing to do) and say what was found.
        None on memory stores, which recover nothing."""
        if not self.opened:
            return None
        t0 = time.perf_counter()
        sizes_found = {lid: ledger.size for lid, ledger in db.ledgers()}
        keep = last_whole_batch(db)
        done = roll_back_to_audit(db, wm, keep, self.genesis_sizes())
        engines = {o["engine"] for o in self.opened.values()}
        return {
            "engine": engines.pop() if len(engines) == 1 else sorted(engines),
            "restarted": any(o["rows"] for o in self.opened.values()),
            "stores": self.opened,
            "ledger_sizes_found": sizes_found,
            "ledger_sizes": {lid: ledger.size
                             for lid, ledger in db.ledgers()},
            "reconciled_to_audit_txn": keep,
            "genesis_sizes": self.genesis_sizes(),
            "reconcile": done,
            "seconds": {
                "open_stores": round(sum(o["open_s"]
                                         for o in self.opened.values()), 3),
                "reconcile": round(time.perf_counter() - t0, 3)}}

    def _replay_genesis_state(self, db, nym, node_handler, wm) -> dict:
        """Replay committed ledger txns through handlers into state (restart
        recovery / genesis seeding; ref ledgers_bootstrap init_state_from_ledger).
        -> {ledger id: txns replayed}, empty when every state was built."""
        handlers = {NYM: nym, NODE: node_handler}
        for h in wm._handlers.values():
            handlers.setdefault(h.txn_type, h)
        replayed = {}
        for lid in (POOL_LEDGER_ID, CONFIG_LEDGER_ID, DOMAIN_LEDGER_ID):
            ledger = db.get_ledger(lid)
            state = db.get_state(lid)
            if state is None or ledger.size == 0:
                continue
            if hasattr(state, "has_root"):
                built = state.committed_head_hash != BLANK_ROOT
            else:
                built = len(state.as_dict(committed=True)) > 0
            if built:
                continue                      # persistent state already built
            replayed[lid] = ledger.size
            for seq_no in range(1, ledger.size + 1):
                txn = ledger.get_by_seq_no(seq_no)
                handler = handlers.get(txn_lib.txn_type_of(txn))
                if handler is not None:
                    handler.update_state(txn, is_committed=True)
            state.commit(state.head_hash)
        return replayed

    @staticmethod
    def _sync_bls_register(register: BlsKeyRegister,
                           pool_manager: TxnPoolManager) -> None:
        for name in pool_manager.node_names:
            register.set_key(name, pool_manager.bls_key_of(name))
