"""Randomized batch verification (RBV) prototype — math + fallback,
validated end-to-end in pure Python. Re-creation of the round-3
analysis artifact cited by docs/performance.md ("Randomized batch
verification (analyzed round 3 — not adopted)"); the hardware-fit
analysis there explains why this is NOT the production kernel (the
device regime measured then was serial-depth bound; RBV buys FLOPs, not depth).

The check (one cofactored equation per batch, random per-batch z_i):

    [8]( [s]B  -  sum_i [z_i]R_i  -  sum_i [c_i]A_i )  ==  identity
    s   = sum_i z_i * S_i  mod L
    c_i = z_i * h_i        mod L,   h_i = SHA512(R_i || A_i || m_i) mod L

Validated here:
  1. all-valid batches accept;
  2. a forged signature fails the batch and is isolated by the log2
     bisection fallback;
  3. the malicious-signer divergence construction (two signatures whose
     individual defects cancel in a FIXED-weight sum) passes the
     deterministic z_i == 1 check and is caught by random z_i —
     the reason the randomness is load-bearing.

Reference anchor: the per-signature verify being batched is the
reference's libsodium path (stp_core/crypto/nacl_wrappers.py:62).

Run:  python probes/rbv_prototype.py      (pure host math, no device)
"""
from __future__ import annotations

import hashlib
import json
import secrets
import sys
import time

sys.path.insert(0, "/root/repo")

from plenum_tpu.ops.ed25519 import (BX, BY, decompress, edwards_add,
                                    edwards_mul, pure_python_sign)

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
B = (BX, BY)
IDENT = (0, 1)


def _neg(pt):
    return ((-pt[0]) % P, pt[1])


def _h_int(r_bytes: bytes, a_bytes: bytes, msg: bytes) -> int:
    return int.from_bytes(hashlib.sha512(r_bytes + a_bytes + msg).digest(),
                          "little") % L


def rbv_check(batch, zs=None) -> bool:
    """batch: [(msg, sig64, pk32), ...] -> one cofactored group check.

    zs overrides the per-item random weights (the divergence demo passes
    all-ones to show why predictable weights are unsound)."""
    if zs is None:
        zs = [secrets.randbits(64) | 1 for _ in batch]
    s = 0
    acc = IDENT
    for (msg, sig, pk), z in zip(batch, zs):
        r_bytes, s_bytes = sig[:32], sig[32:]
        r_pt = decompress(r_bytes)
        a_pt = decompress(pk)
        if r_pt is None or a_pt is None:
            return False
        s = (s + z * int.from_bytes(s_bytes, "little")) % L
        c = (z * _h_int(r_bytes, pk, msg)) % L
        acc = edwards_add(acc, edwards_mul(z % L, r_pt))
        acc = edwards_add(acc, edwards_mul(c, a_pt))
    total = edwards_add(edwards_mul(s, B), _neg(acc))
    for _ in range(3):                      # [8]: clear the cofactor
        total = edwards_add(total, total)
    return total == IDENT


def rbv_verify_with_fallback(batch):
    """-> (ok_flags, n_group_checks). Batch check first; on failure,
    bisect to isolate the bad indices in ~log2(n) checks per forgery."""
    checks = [0]

    def go(lo, hi):
        checks[0] += 1
        sub = batch[lo:hi]
        if rbv_check(sub):
            return [True] * (hi - lo)
        if hi - lo == 1:
            return [False]
        mid = (lo + hi) // 2
        return go(lo, mid) + go(mid, hi)

    return go(0, len(batch)), checks[0]


def _make_batch(n, forge=()):
    out = []
    for i in range(n):
        seed = (b"rbv%d" % i).ljust(32, b"\0")
        msg = b"message-%d" % i
        sig, pk = pure_python_sign(seed, msg)
        if i in forge:
            sig = sig[:32] + ((int.from_bytes(sig[32:], "little") + 7) % L
                              ).to_bytes(32, "little")
        out.append((msg, sig, pk))
    return out


def _divergent_pair():
    """Two individually-invalid signatures whose S-defects cancel under
    EQUAL weights: S1' = S1 + d, S2' = S2 - d."""
    batch = _make_batch(2)
    d = 12345
    (m1, s1, p1), (m2, s2, p2) = batch
    s1 = s1[:32] + ((int.from_bytes(s1[32:], "little") + d) % L
                    ).to_bytes(32, "little")
    s2 = s2[:32] + ((int.from_bytes(s2[32:], "little") - d) % L
                    ).to_bytes(32, "little")
    return [(m1, s1, p1), (m2, s2, p2)]


def main():
    t0 = time.perf_counter()
    # 1. all-valid accepts
    good = _make_batch(16)
    assert rbv_check(good)
    flags, checks = rbv_verify_with_fallback(good)
    assert all(flags) and checks == 1

    # 2. forged members isolated in ~log2 bisection checks
    forged = _make_batch(16, forge={5, 11})
    flags, checks = rbv_verify_with_fallback(forged)
    assert [i for i, f in enumerate(flags) if not f] == [5, 11]
    assert checks <= 1 + 2 * 2 * 5        # 2 forgeries x ~2log2(16)+1

    # 3. divergence: cancels under fixed weights, caught by random z
    div = _divergent_pair()
    assert rbv_check(div, zs=[1, 1]), "construction should cancel at z=1"
    caught = sum(not rbv_check(div) for _ in range(20))
    assert caught == 20, f"random z missed the divergent pair {20-caught}x"

    print(json.dumps({
        "probe": "rbv_prototype",
        "all_valid_accepts": True,
        "forged_isolated": [5, 11],
        "bisection_checks": checks,
        "divergent_pair_passes_fixed_z": True,
        "divergent_pair_caught_by_random_z": "20/20",
        "wall_s": round(time.perf_counter() - t0, 2),
    }))


if __name__ == "__main__":
    main()
