"""What every launcher kind shares: keys and requests from the seed, the
preload, the device report, the native-library gate."""
from __future__ import annotations

import time

from benchmarks import traffic


def device_report() -> dict:
    """The device as JAX reports it in THIS process (which thereby owns
    the chip), with the peak bytes in use on the fullest chip."""
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def require_native() -> None:
    """A pool on pure-Python pairings or a pure-Python state codec is a
    different system, and the fallbacks in plenum_tpu.native are silent."""
    from plenum_tpu.native import have_native_bn254
    from plenum_tpu.state import native_codec
    from plenum_tpu.storage.kv_native import native_available
    got = {"bn254": have_native_bn254(), "kv": native_available(),
           "mpt_codec": native_codec.available()}
    missing = [k for k, ok in got.items() if not ok]
    if missing:
        raise SystemExit(f"benchmark: native libraries did not load: "
                         f"{missing}")


class Identities:
    """The trustee, the preloaded DIDs and the DIDs the stream creates,
    all derived from the seed. Signers are made on first use."""

    def __init__(self, seed: int, preload: int, trustee):
        self.seed, self.preload, self.trustee = seed, preload, trustee
        self._signers: dict = {}

    def _signer(self, tag: str, i: int, seed: int | None = None):
        from plenum_tpu.crypto.ed25519 import Ed25519Signer
        key = (tag, i, seed)
        if key not in self._signers:
            self._signers[key] = Ed25519Signer(seed=traffic.did_seed(
                self.seed if seed is None else seed, tag, i))
        return self._signers[key]

    def did(self, i: int):
        return self._signer("did", i)

    def new(self, i: int, seed: int):
        return self._signer("new", i, seed)

    def genesis_nyms(self, first_seq_no: int) -> list[dict]:
        """The preload: one NYM txn per DID, appended to the domain
        genesis so the ledger and the state have an age at window open."""
        from plenum_tpu.execution import txn as txn_lib
        from plenum_tpu.execution.txn import NYM
        out = []
        for i in range(self.preload):
            u = self.did(i)
            txn = txn_lib.new_txn(NYM, {"dest": u.identifier,
                                        "verkey": u.verkey_b58})
            txn["txn"].setdefault("metadata", {})["from"] = \
                self.trustee.identifier
            txn_lib.set_seq_no(txn, first_seq_no + i)
            out.append(txn)
        return out

    def sign(self, ops, seed: int, req_id_base: int) -> list:
        """Plans -> signed Requests. Request ids are unique per signer
        over every window of the process (req_id_base moves on)."""
        from plenum_tpu.common.request import Request
        from plenum_tpu.execution.txn import ATTRIB, NYM
        out = []
        for n, op in enumerate(ops):
            rid = req_id_base + n
            if op.kind == "NYM":
                new = self.new(op.target, seed)
                signer, body = self.trustee, {
                    "type": NYM, "dest": new.identifier,
                    "verkey": new.verkey_b58}
            elif op.kind == "ATTRIB":
                signer = self.did(op.signer)
                body = {"type": ATTRIB, "dest": signer.identifier,
                        "raw": op.raw}
            else:
                raise SystemExit(f"benchmark: no signer for a {op.kind} "
                                 f"operation (reads have no cell yet)")
            req = Request(signer.identifier, rid, body)
            req.signature = signer.sign_b58(req.signing_bytes())
            out.append(req)
        return out


class Split:
    """The set-up split: seconds by phase, in the order they ran."""

    def __init__(self):
        self.parts: dict[str, float] = {}
        self._t = time.perf_counter()

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = round(self.parts.get(name, 0.0) + now - self._t, 3)
        self._t = now
