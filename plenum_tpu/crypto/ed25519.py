"""Ed25519 provider seam: sign + batched verify with cpu and jax backends.

Reference behavior: stp_core/crypto/nacl_wrappers.py:179,212 (Signer/Verifier
over libsodium) and plenum/server/client_authn.py:273 (CoreAuthNr verifying
every propagated request on every node — the primary hot spot).

The seam's contract is batch-first (SURVEY.md §7 stage 2): callers hand a
vector of (message, signature, verkey) and get a verdict vector back. The cpu
backend loops over the C library; the jax backend stages the whole batch into
one device dispatch of the double-scalar-mult kernel (plenum_tpu/ops/ed25519).
Invalid encodings (bad point, S >= L) are rejected host-side and never reach
the device.
"""
from __future__ import annotations

import hashlib
from abc import ABC, abstractmethod
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Optional, Sequence

import jax
import numpy as np

from plenum_tpu.utils.base58 import b58encode

try:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey, Ed25519PublicKey)
    from cryptography.exceptions import InvalidSignature
    _HAVE_CRYPTOGRAPHY = True
except Exception:  # pragma: no cover
    _HAVE_CRYPTOGRAPHY = False

from plenum_tpu.ops import aot as _aot, ed25519 as _ops

VerifyItem = tuple[bytes, bytes, bytes]   # (message, signature64, verkey32)


class _JaxToken:
    """In-flight device verification: the dispatched verdict array plus the
    mapping back to the caller's item order."""

    __slots__ = ("ok", "idxs", "n")

    def __init__(self, ok, idxs, n):
        self.ok = ok
        self.idxs = idxs
        self.n = n

    @property
    def lanes(self) -> int:
        """Padded rows of the dispatch: which program ran it."""
        return int(self.ok.shape[0])


class Ed25519Signer:
    """Deterministic Ed25519 signing from a 32-byte seed.

    Uses the C library when `cryptography` is importable; otherwise falls
    back to the package's own RFC 8032 implementation (ops/ed25519
    extended-coordinate ladder, ~4 ms/sign) so nothing above this seam
    needs the dependency."""

    def __init__(self, seed: Optional[bytes] = None):
        import os
        self._seed = seed if seed is not None else os.urandom(32)
        assert len(self._seed) == 32
        if _HAVE_CRYPTOGRAPHY:
            self._sk = Ed25519PrivateKey.from_private_bytes(self._seed)
            from cryptography.hazmat.primitives import serialization
            self._vk = self._sk.public_key().public_bytes(
                serialization.Encoding.Raw, serialization.PublicFormat.Raw)
        else:
            self._sk = None
            h = hashlib.sha512(self._seed).digest()
            a = int.from_bytes(h[:32], "little")
            a &= (1 << 254) - 8
            a |= 1 << 254
            self._pp_scalar, self._pp_prefix = a, h[32:]
            self._vk = _ops.compress(
                _ops.ext_scalar_mul(a, (_ops.BX, _ops.BY)))

    @property
    def seed(self) -> bytes:
        return self._seed

    @property
    def verkey(self) -> bytes:
        return self._vk

    @property
    def verkey_b58(self) -> str:
        return b58encode(self._vk)

    @property
    def identifier(self) -> str:
        """DID-style identifier: base58 of the first 16 verkey bytes (as indy)."""
        return b58encode(self._vk[:16])

    def sign(self, msg: bytes) -> bytes:
        if self._sk is not None:
            return self._sk.sign(msg)
        r = int.from_bytes(hashlib.sha512(self._pp_prefix + msg).digest(),
                           "little") % _ops.L
        r_enc = _ops.compress(_ops.ext_scalar_mul(r, (_ops.BX, _ops.BY)))
        k = int.from_bytes(hashlib.sha512(r_enc + self._vk + msg).digest(),
                           "little") % _ops.L
        s = (r + k * self._pp_scalar) % _ops.L
        return r_enc + s.to_bytes(32, "little")

    def sign_b58(self, msg: bytes) -> str:
        return b58encode(self.sign(msg))


class Ed25519Verifier(ABC):
    @abstractmethod
    def verify_batch(self, items: Sequence[VerifyItem]) -> np.ndarray:
        """-> bool[N] verdicts; NEVER raises on malformed input."""

    def verify(self, msg: bytes, sig: bytes, vk: bytes) -> bool:
        return bool(self.verify_batch([(msg, sig, vk)])[0])

    # --- async pipelining seam -------------------------------------------
    # The device backend overrides these so a caller can overlap the device
    # round-trip with other work (accumulate-then-flush, SURVEY.md §7):
    # submit returns immediately after dispatch; collect(wait=False) returns
    # None while the device is still computing. The default (CPU) behavior
    # computes at submit, so collect is always immediately ready.

    def submit_batch(self, items: Sequence[VerifyItem]):
        return self.verify_batch(items)

    def collect_batch(self, token, wait: bool = True) -> Optional[np.ndarray]:
        return token

    def preload(self, waves: Iterable[tuple[int, int]]) -> list:
        """Warm-up hint: the (items, distinct verkeys) of every wave a
        prewarm is about to dispatch. A device backend obtains those
        programs now, all at once; a host backend has none."""
        return []


_VK_VALID_CACHE: dict[bytes, bool] = {}
# verkey -> decompressible. The modular sqrt inside decompress costs ~140 us
# of pure Python per call — more than the OpenSSL verify itself — and real
# traffic re-uses verkeys heavily (every request from a client carries the
# same key). The verdict is a pure function of the 32 bytes, so caching can
# never change a verdict, only skip recomputation. Bounded: reset at 8192
# entries (a pool sees far fewer distinct signers between resets).


def _vk_decompressible(vk: bytes) -> bool:
    got = _VK_VALID_CACHE.get(vk)
    if got is None:
        if len(_VK_VALID_CACHE) >= 8192:
            _VK_VALID_CACHE.clear()
        got = _VK_VALID_CACHE[vk] = _ops.decompress(vk) is not None
    return got


def _precheck(msg, sig, vk) -> bool:
    """Canonicality checks shared by BOTH backends so they can never disagree
    (a backend-verdict split on the same bytes would fork a BFT pool):
    reject non-canonical point encodings (y >= p) and S >= L, which OpenSSL
    accepts but RFC 8032 strict verification rejects."""
    try:
        if len(sig) != 64 or len(vk) != 32 or not isinstance(
                msg, (bytes, bytearray, memoryview)):
            return False
        if not _vk_decompressible(bytes(vk)):
            return False
        # R is deliberately NOT validated here: both backends resolve a bad R
        # by the recomputed-R' byte compare (ref10 semantics), so the verdicts
        # still agree and the hot path skips a per-signature modular sqrt.
        return int.from_bytes(bytes(sig[32:]), "little") < _ops.L
    except Exception:
        return False


def content_digest(*parts: bytes) -> bytes:
    """THE length-prefixed content digest for every verdict cache in the
    package (this module, crypto/bls.py, parallel/crypto_service.py).
    The prefixes are load-bearing: without them an attacker could shift
    bytes between adjacent fields ((msg, sig+vk[:1], vk[1:]) would hash
    like the honest triple), pre-poison a False verdict, and make every
    cache user reject a validly signed input."""
    h = hashlib.sha256()
    for part in parts:
        h.update(len(part).to_bytes(4, "big"))
        h.update(part)
    return h.digest()


def verdict_cache_put(cache: dict, maxsize: int, key: bytes,
                      verdict: bool) -> bool:
    """Bounded FIFO insert shared by the verdict caches (attacker-supplied
    content must never grow them without bound); returns the verdict.
    Tolerates concurrent callers (the crypto service verifies BLS in
    executor threads): a key another thread already evicted is skipped,
    not raised."""
    if len(cache) >= maxsize:
        for k in list(cache)[:maxsize // 8]:
            cache.pop(k, None)
    cache[key] = verdict
    return verdict


# Process-wide verdict cache shared by every CpuEd25519Verifier: in a
# co-hosted topology (the in-process pool, or several nodes embedded in
# one OS process) each node verifies the same client signature once —
# identical content, identical verdict — so the 2nd..nth node rides the
# 1st's result. Single-node processes pay one sha256 (~1 us) against a
# ~110 us verify.
_CPU_VERDICTS: dict[bytes, bool] = {}
_CPU_VERDICTS_MAX = 65536


class CpuEd25519Verifier(Ed25519Verifier):
    """Scalar loop over the C library — the measured CPU baseline. Without
    `cryptography` it degrades to the package's own RFC 8032 verifier
    (ops.pure_python_verify, ~2 ms/sig): slower, but verdict-identical —
    both run strict checks behind the shared _precheck, so a mixed pool
    cannot fork on backend choice."""

    def __init__(self):
        # verkey bytes -> parsed OpenSSL key object; parsing costs ~12 us
        # per call and keys repeat per client. Bounded like _VK_VALID_CACHE.
        self._pk_cache: dict = {}

    def _pk(self, vk: bytes) -> Ed25519PublicKey:
        pk = self._pk_cache.get(vk)
        if pk is None:
            if len(self._pk_cache) >= 8192:
                self._pk_cache.clear()
            pk = self._pk_cache[vk] = \
                Ed25519PublicKey.from_public_bytes(vk)
        return pk

    def evict_key(self, vk) -> None:
        """Key rotation: drop the rotated-out key's parsed object."""
        if isinstance(vk, bytes):
            self._pk_cache.pop(vk, None)

    def verify_batch(self, items: Sequence[VerifyItem]) -> np.ndarray:
        out = np.zeros(len(items), dtype=bool)
        for i, (msg, sig, vk) in enumerate(items):
            try:
                msg, sig, vk = bytes(msg), bytes(sig), bytes(vk)
            except Exception:
                continue      # contract: malformed input is a False verdict
            key = content_digest(msg, sig, vk)
            hit = _CPU_VERDICTS.get(key)
            if hit is not None:
                out[i] = hit
                continue
            ok = False
            if _precheck(msg, sig, vk):
                if _HAVE_CRYPTOGRAPHY:
                    try:
                        self._pk(vk).verify(sig, msg)
                        ok = True
                    except Exception:
                        ok = False
                else:
                    ok = _ops.pure_python_verify(msg, sig, vk)
            out[i] = verdict_cache_put(_CPU_VERDICTS, _CPU_VERDICTS_MAX,
                                       key, ok)
        return out


# The small bucket: the key table's small size, the rings' first pad bucket,
# and the lane count of the small verify program a service holds beside its
# large one (parallel/crypto_service.py). 64 and 128 lanes take the same
# 4.7 ms on a v5e (PERF.md section 5), so nothing smaller is worth a load.
SMALL_LANES = 64


def _bytes_avals(m_pad: int, u_pad: int) -> tuple:
    """The abstract signature of one compressed dispatch, as
    `_dispatch_bytes` stages it: S, h, key table, key index, R."""
    rows = jax.ShapeDtypeStruct((m_pad, 32), np.uint8)
    return (rows, rows, jax.ShapeDtypeStruct((u_pad, 32), np.uint8),
            jax.ShapeDtypeStruct((m_pad,), np.int32), rows)


class JaxEd25519Verifier(Ed25519Verifier):
    """Batched device verification.

    Host prep per item: split sig into (R, S); decompress A once per verkey
    (cached as ready-to-ship limb rows for the four quarter points
    [2^64k](-A) of the split window ladder, kept in extended coordinates so
    the 192-doubling chain needs NO host inversions); reject non-canonical
    S or invalid A; h = SHA512(R||A||M) mod L. R is NOT decompressed — the
    kernel recomputes R' and compares its compressed form against the raw
    signature bytes (ref10 semantics), so the only per-item bigint work
    left on host is one sha512 and one mod-L reduction.
    Device: one verify_kernel dispatch over the padded batch.
    """

    # Compressed dispatch (round 5): ship RAW BYTES (32 B S + 32 B h +
    # 32 B R + 4 B key index per signature, 32 B per distinct verkey) and
    # let the device decompress keys, unpack digits, and build the window
    # tables per KEY instead of per signature. ~4.7x fewer bytes per
    # signature and 40x per key on a link that is ~80% of dispatch cost —
    # and the pure-Python per-new-verkey host work (modular sqrt + 192
    # bigint doublings, ~1 ms) disappears from the 1-core host entirely.
    # The sharded plane keeps the limb-staged path until its SPMD program
    # is ported (it overrides _device_verify on the staged arrays).
    _compressed_dispatch = True

    def __init__(self, min_batch: int = 1, cache_size: int = 65536,
                 device=None):
        # verkeys are attacker-supplied; the cache must be bounded (FIFO
        # evict). value: int32[4, 4, NLIMB] quarter-point rows, or None
        # for invalid keys
        self._pt_cache: dict[bytes, Optional[np.ndarray]] = {}
        self._cache_size = cache_size
        self._min_batch = min_batch
        # multi-device lane pinning (ops.ed25519.stage_on): every dispatch
        # commits its staged arrays to THIS chip, so N verifiers over N
        # devices run N concurrent kernel executions — the per-lane
        # sharding seam the multi-device pipeline builds on. None = the
        # backend default device (single-chip behavior, unchanged).
        self.device = device
        # (m_pad, u_pad) -> the executable preload() obtained for it;
        # _device_verify_bytes looks here before the jitted kernel
        self._preloaded: dict[tuple[int, int], object] = {}
        # their lane counts, ascending: what _pad_sizes pads to. Replaced
        # whole by preload(), because a dispatch reads it from another
        # thread (the service's worker) than the one that preloads
        self._held_lanes: tuple[int, ...] = ()

    def _neg_a_limbs(self, vk: bytes) -> Optional[np.ndarray]:
        if vk in self._pt_cache:
            return self._pt_cache[vk]
        a = _ops.decompress(vk)
        if a is None:
            rows = None
        else:
            neg = ((_ops.P - a[0]) % _ops.P, a[1])         # -A = (-x, y)
            rows = _ops.ext_quarters(neg)
        if len(self._pt_cache) >= self._cache_size:
            self._pt_cache.pop(next(iter(self._pt_cache)))
        self._pt_cache[vk] = rows
        return rows

    # kept for tests/back-compat: cached decompression of a verkey
    def _decompress_cached(self, vk: bytes):
        rows = self._neg_a_limbs(vk)
        if rows is None:
            return None
        x = _ops.limbs_to_int(rows[0, 0])
        y = _ops.limbs_to_int(rows[0, 1])
        return ((_ops.P - x) % _ops.P, y)

    def evict_key(self, vk) -> None:
        """Key rotation: drop a rotated-out verkey's staged quarter-point
        rows from the key table (see BlsCryptoVerifier.evict_key)."""
        if isinstance(vk, bytes):
            self._pt_cache.pop(vk, None)

    def _dispatch(self, items: Sequence[VerifyItem]):
        if self._compressed_dispatch:
            return self._dispatch_bytes(items)
        return self._dispatch_limbs(items)

    def _dispatch_bytes(self, items: Sequence[VerifyItem]):
        """Host staging for the compressed-dispatch kernel: per item one
        sha512 + one mod-L reduction; everything ships as raw bytes.
        Invalid verkeys are NOT screened here — the device's decompression
        validity mask forces their verdicts False (same verdict the cpu
        backend's host precheck gives, so backends can never disagree)."""
        n = len(items)
        verdict = np.zeros(n, dtype=bool)
        if n == 0:
            return verdict
        idxs: list[int] = []
        s_vals: list[bytes] = []
        h_vals: list[bytes] = []
        r_enc: list[bytes] = []
        uniq: dict[bytes, int] = {}
        u_keys: list[bytes] = []
        a_idx: list[int] = []
        for i, (msg, sig, vk) in enumerate(items):
            try:
                msg, sig, vk = bytes(msg), bytes(sig), bytes(vk)
                if len(sig) != 64 or len(vk) != 32:
                    continue
                if int.from_bytes(sig[32:], "little") >= _ops.L:
                    continue
                h = int.from_bytes(
                    hashlib.sha512(sig[:32] + vk + msg).digest(),
                    "little") % _ops.L
            except Exception:
                continue    # contract: malformed input is a False verdict
            u = uniq.get(vk)
            if u is None:
                u = uniq[vk] = len(u_keys)
                u_keys.append(vk)
            idxs.append(i)
            s_vals.append(sig[32:])
            h_vals.append(h.to_bytes(32, "little"))
            r_enc.append(sig[:32])
            a_idx.append(u)
        if not idxs:
            return verdict                     # all malformed: ready ndarray
        m_pad, u_pad = self._pad_sizes(len(idxs), len(u_keys))
        pad = m_pad - len(idxs)
        # padding repeats the first row; its verdict is discarded
        s_vals += [s_vals[0]] * pad
        h_vals += [h_vals[0]] * pad
        r_enc += [r_enc[0]] * pad
        a_idx += [a_idx[0]] * pad
        u_keys += [u_keys[0]] * (u_pad - len(u_keys))
        s_u8 = np.frombuffer(b"".join(s_vals), np.uint8).reshape(m_pad, 32)
        h_u8 = np.frombuffer(b"".join(h_vals), np.uint8).reshape(m_pad, 32)
        r_u8 = np.frombuffer(b"".join(r_enc), np.uint8).reshape(m_pad, 32)
        k_u8 = np.frombuffer(b"".join(u_keys), np.uint8).reshape(u_pad, 32)
        idx = np.asarray(a_idx, dtype=np.int32)
        ok = self._device_verify_bytes(s_u8, h_u8, k_u8, idx, r_u8)
        return _JaxToken(ok, idxs, n)

    @property
    def min_batch(self) -> int:
        return self._min_batch

    @staticmethod
    def _program_for(m: int, n_keys: int) -> tuple[int, int]:
        """The tightest program that fits a wave: rows to the next pow-2;
        the unique-key table to exactly TWO buckets per batch shape —
        {64-key, full} — so a drifting active-client count costs at most
        two multi-minute compiles, not one per pow-2 step."""
        m_pad = 1
        while m_pad < m:
            m_pad *= 2
        small = min(SMALL_LANES, m_pad)    # u <= m <= m_pad always holds
        return m_pad, (small if n_keys <= small else m_pad)

    def _pad_sizes(self, m: int, n_keys: int) -> tuple[int, int]:
        """THE batch-shape bucketing policy, shared by both staging paths
        (a divergence would double the compile-shape set): batch rows pad
        to the smallest lane count among the programs preload() HOLDS
        that fits the wave; where none is held or none fits, to the next
        pow-2 >= min_batch. The key table keeps its two buckets a batch
        shape. So a service that holds 64 and 512 lanes runs a wave of
        nine in the 64-lane program; a ring packs its waves to its own
        pinned ladder before they get here, so its m is a held lane
        count already."""
        m_pad = next((lanes for lanes in self._held_lanes if lanes >= m),
                     max(m, self._min_batch))
        return self._program_for(m_pad, n_keys)

    def _device_verify_bytes(self, s_u8, h_u8, k_u8, idx, r_u8):
        kernel = self._preloaded.get((s_u8.shape[0], k_u8.shape[0]),
                                     _ops.verify_kernel_bytes)
        return kernel(
            *_ops.stage_on(self.device, s_u8, h_u8, k_u8, idx, r_u8))

    def in_store(self, waves: Iterable[tuple[int, int]]) -> dict:
        """{(m_pad, u_pad): does the executable store hold that program
        for this verifier's device?} — what preload() would load rather
        than compile. Touches the backend: the chip's owner only."""
        return {shape: _aot.has_entry(_ops.verify_kernel_bytes,
                                      _bytes_avals(*shape), self.device)
                for shape in sorted({self._program_for(n, keys)
                                     for n, keys in waves})}

    def preload(self, waves: Iterable[tuple[int, int]]) -> list:
        """Obtain the verify program of every wave a prewarm is about to
        dispatch through the executable store (ops/aot.py): on a machine
        that compiled them before, each is loaded without entering the
        kernel's Python body; otherwise it is traced and compiled as ever
        and stored for the next process. The warm-up waves that follow
        prove each one answers; a shape nobody preloaded still traces
        and compiles on its first dispatch.
        -> the (m_pad, u_pad) shapes obtained by this call.

        Each wave asks for the tightest program that fits it, whatever
        min_batch says: what is held here is what `_pad_sizes` pads to,
        and min_batch is only the floor where nothing held fits.

        Stored programs are loaded ON THE CALLING THREAD, one after the
        other; the rest compile at once, one thread each (XLA compiles
        with the GIL released), except those another process of this
        machine is compiling already, whose entries are waited for and
        loaded on the calling thread too. Measured on a v5e (PR 26): one
        PjRt load issued from the process's main thread takes ~13 s, the
        next ~7 s, whatever the shape; the same load issued from another
        thread takes 50-75 s, and two side by side ~50 s each. So call
        this from the main thread.

        A subclass that re-routes the dispatch (the sharded plane's SPMD
        program, a test double) never runs these programs, so it gets
        none."""
        cls = type(self)
        if (not self._compressed_dispatch
                or cls.submit_batch is not JaxEd25519Verifier.submit_batch
                or cls._device_verify_bytes
                is not JaxEd25519Verifier._device_verify_bytes):
            return []
        stored = {shape: held for shape, held in self.in_store(waves).items()
                  if shape not in self._preloaded}

        def obtain(shape, wait=True):
            return _aot.obtain(_ops.verify_kernel_bytes,
                               _bytes_avals(*shape), self.device, wait=wait)

        def compile_unless_claimed(shape):
            try:
                return obtain(shape, wait=False)
            except _aot.ClaimedElsewhere:
                return None

        def hold(shape, exe):
            self._preloaded[shape] = exe
            self._held_lanes = tuple(sorted(
                {lanes for lanes, _ in self._preloaded}))

        for shape, held in stored.items():
            if held:
                hold(shape, obtain(shape))
        missing = [shape for shape, held in stored.items() if not held]
        if missing:
            with ThreadPoolExecutor(len(missing)) as pool:
                got = list(pool.map(compile_unless_claimed, missing))
            for shape, exe in zip(missing, got):
                # another process of this machine is compiling that one
                # (validators started together want the same programs):
                # wait for its entry and load it HERE, not in a worker
                hold(shape, exe or obtain(shape))
        return list(stored)

    def _dispatch_limbs(self, items: Sequence[VerifyItem]):
        n = len(items)
        verdict = np.zeros(n, dtype=bool)
        if n == 0:
            return verdict
        idxs, s_vals, h_vals, r_enc = [], [], [], []
        # verkeys repeat heavily in pool traffic, and their quarter-point
        # rows are 73% of the dispatch bytes — ship one row per DISTINCT
        # key plus an index vector, gathered on device
        uniq: dict[bytes, int] = {}
        u_rows: list[np.ndarray] = []
        a_idx: list[int] = []
        for i, (msg, sig, vk) in enumerate(items):
            try:
                msg, sig, vk = bytes(msg), bytes(sig), bytes(vk)
                if len(sig) != 64 or len(vk) != 32:
                    continue
                rows = self._neg_a_limbs(vk)
                if rows is None:
                    continue
                s = int.from_bytes(sig[32:], "little")
                if s >= _ops.L:
                    continue
                h = int.from_bytes(
                    hashlib.sha512(sig[:32] + vk + msg).digest(), "little") % _ops.L
            except Exception:
                continue    # contract: malformed input is a False verdict
            u = uniq.get(vk)
            if u is None:
                u = uniq[vk] = len(u_rows)
                u_rows.append(rows)
            idxs.append(i)
            s_vals.append(s)
            h_vals.append(h)
            a_idx.append(u)
            r_enc.append(sig[:32])
        if not idxs:
            return verdict                     # all malformed: ready ndarray
        m_pad, u_pad = self._pad_sizes(len(idxs), len(u_rows))
        pad = m_pad - len(idxs)
        # padding repeats the first row; its verdict is discarded
        s_vals += [s_vals[0]] * pad
        h_vals += [h_vals[0]] * pad
        a_idx += [a_idx[0]] * pad
        r_enc += [r_enc[0]] * pad
        u_rows += [u_rows[0]] * (u_pad - len(u_rows))
        qmask = (1 << _ops.QUARTER_SHIFT) - 1
        s_digits = _ops.scalar_windows(s_vals, _ops.N_COMB, _ops.CBITS)
        h_digits = np.stack([
            _ops.scalar_windows(
                [(h >> (_ops.QUARTER_SHIFT * q)) & qmask for h in h_vals],
                _ops.N_WIN)
            for q in range(_ops.N_QUARTERS)], axis=1)   # [N_WIN, 4, m]
        aq_unique = np.stack(u_rows)                    # [U, 4, 4, NLIMB]
        idx = np.asarray(a_idx, dtype=np.int32)         # [m]
        ry, r_sign = _ops.r_bytes_to_limbs(r_enc)
        ok = self._device_verify(s_digits, h_digits, aq_unique, idx,
                                 ry, r_sign)
        return _JaxToken(ok, idxs, n)

    def _device_verify(self, s_digits, h_digits, aq_unique, idx, ry, r_sign):
        """Staged host arrays -> flat verdict array on device. Subclasses
        re-route the dispatch (ShardedJaxEd25519Verifier shards it over a
        mesh); the host staging above is identical either way."""
        return _ops.verify_kernel_indexed(
            *_ops.stage_on(self.device, s_digits, h_digits, aq_unique,
                           idx, ry, r_sign))

    def rewarm(self) -> None:
        """Plane-supervisor re-warm hook: drop the staged key material so
        the next dispatch re-uploads it. After a device/runtime restart the
        host-side caches describe uploads the device no longer holds;
        re-staging them is the cheap insurance that a re-admitted device
        starts from a known-good session."""
        self._pt_cache.clear()

    # verify_batch = submit + blocking collect; submit_batch returns right
    # after the (asynchronous) device dispatch
    def submit_batch(self, items: Sequence[VerifyItem]):
        return self._dispatch(items)

    def collect_batch(self, token, wait: bool = True) -> Optional[np.ndarray]:
        if isinstance(token, np.ndarray):
            return token                       # empty/hard-fail fast path
        if not wait and not token.ok.is_ready():
            return None
        ok = np.asarray(token.ok)
        verdict = np.zeros(token.n, dtype=bool)
        for j, i in enumerate(token.idxs):
            verdict[i] = bool(ok[j])
        return verdict

    def verify_batch(self, items: Sequence[VerifyItem]) -> np.ndarray:
        return self.collect_batch(self.submit_batch(items), wait=True)


def make_verifier(backend: str, min_batch: int = 1,
                  supervised: Optional[bool] = None) -> Ed25519Verifier:
    """min_batch (jax only): pad every dispatch to at least this power of
    two, unless a smaller program that fits it was preloaded (`_pad_sizes`).
    A pool node should pick one bucket covering its receive quotas so
    no novel program shape appears under load — a recompile at one
    costs minutes (tracing + XLA:TPU compilation) and starves the prod loop.

    Every DEVICE-backed verifier (jax, jax-sharded, service) comes wrapped
    in the plane supervisor (parallel/supervisor.py): circuit breaker to
    CPU fallback, adaptive deadlines with hedged dispatch, and bounded
    in-flight backpressure — a wedged device degrades the node to CPU
    speed instead of stalling it (the round-5 device blackout). Pass
    supervised=False (or set PLENUM_CRYPTO_SUPERVISOR=0) for the bare
    verifier."""
    def _wrap(device):
        if supervised is False:
            return device
        from plenum_tpu.parallel.supervisor import supervise
        return supervise(device)

    if backend == "jax":
        return _wrap(JaxEd25519Verifier(min_batch=min_batch))
    if backend == "jax-sharded":
        # deferred: parallel/ pulls in jax.sharding + the SPMD plane
        from plenum_tpu.parallel.crypto_plane import make_sharded_verifier
        return _wrap(make_sharded_verifier(min_batch=min_batch))
    if backend == "service":
        # cross-process crypto plane: the device has ONE owner process
        # and co-hosted nodes ship batches to it (socket path from
        # PLENUM_CRYPTO_SOCKET); see parallel/crypto_service.py
        from plenum_tpu.parallel.crypto_service import ServiceEd25519Verifier
        return _wrap(ServiceEd25519Verifier())
    return CpuEd25519Verifier()
