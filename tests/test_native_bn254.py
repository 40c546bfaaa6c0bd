"""Differential tests: the C++ BN254 library vs the pure-Python twin.

The native library (plenum_tpu/native/bn254.cpp) carries the 3PC BLS hot
path; the Python implementation (crypto/bn254.py) is the authoritative
reference. Every exported operation is checked against it on random inputs —
the correctness bar SURVEY.md §7 sets for native pairing code.
"""
import ctypes
import random

import pytest

from plenum_tpu.crypto import bn254 as c
from plenum_tpu.crypto.bn254 import _dec_g1, _dec_g2, _enc_g1, _enc_g2
from plenum_tpu.native import bn254_lib, have_native_bn254

pytestmark = pytest.mark.skipif(not have_native_bn254(),
                                reason="native toolchain unavailable")

rng = random.Random(0xB254)


def py_g1_mul(a, k):
    out = None
    while k:
        if k & 1:
            out = c.g1_add(out, a)
        a = c.g1_add(a, a)
        k >>= 1
    return out


def py_g2_mul(a, k):
    out = None
    while k:
        if k & 1:
            out = c.g2_add(out, a)
        a = c.g2_add(a, a)
        k >>= 1
    return out


def f12_to_bytes(f):
    (a, b, d), (e, g, h) = f
    vals = [a[0], a[1], b[0], b[1], d[0], d[1],
            e[0], e[1], g[0], g[1], h[0], h[1]]
    return b"".join(x.to_bytes(32, "big") for x in vals)


def f12_from_bytes(raw):
    v = [int.from_bytes(raw[i * 32:(i + 1) * 32], "big") for i in range(12)]
    return (((v[0], v[1]), (v[2], v[3]), (v[4], v[5])),
            ((v[6], v[7]), (v[8], v[9]), (v[10], v[11])))


def test_g1_mul_differential():
    for _ in range(5):
        k = rng.randrange(1, c.R)
        assert c.g1_mul(c.G1_GEN, k) == py_g1_mul(c.G1_GEN, k)


def test_g2_mul_differential():
    for _ in range(2):
        k = rng.randrange(1, c.R)
        assert c.g2_mul(c.G2_GEN, k) == py_g2_mul(c.G2_GEN, k)


def test_g1_g2_add_differential():
    a = c.g1_mul(c.G1_GEN, 7)
    b = c.g1_mul(c.G1_GEN, 11)
    buf = ctypes.create_string_buffer(64)
    assert bn254_lib.pc_g1_add(_enc_g1(a), _enc_g1(b), buf) == 0
    assert _dec_g1(buf.raw) == c.g1_add(a, b)
    qa = c.g2_mul(c.G2_GEN, 7)
    qb = c.g2_mul(c.G2_GEN, 11)
    buf2 = ctypes.create_string_buffer(128)
    assert bn254_lib.pc_g2_add(_enc_g2(qa), _enc_g2(qb), buf2) == 0
    assert _dec_g2(buf2.raw) == c.g2_add(qa, qb)


def test_miller_loop_differential():
    p1 = c.g1_mul(c.G1_GEN, 123)
    q2 = c.g2_mul(c.G2_GEN, 45)
    buf = ctypes.create_string_buffer(384)
    assert bn254_lib.pc_miller(_enc_g2(q2), _enc_g1(p1), buf) == 0
    assert f12_from_bytes(buf.raw) == c.miller_loop(q2, p1)


def test_final_exp_differential():
    m = c.miller_loop(c.g2_mul(c.G2_GEN, 9), c.g1_mul(c.G1_GEN, 31))
    buf = ctypes.create_string_buffer(384)
    assert bn254_lib.pc_final_exp(f12_to_bytes(m), buf) == 0
    assert f12_from_bytes(buf.raw) == c.final_exponentiation(m)


def test_pairing_check_bilinearity_random():
    for _ in range(3):
        a = rng.randrange(1, c.R)
        b = rng.randrange(1, c.R)
        ok = c.pairing_check([
            (c.g2_mul(c.G2_GEN, a), c.g1_mul(c.G1_GEN, b)),
            (c.g2_mul(c.G2_GEN, a * b % c.R), c.g1_neg(c.G1_GEN))])
        assert ok


def test_pairing_check_rejects_wrong():
    p1 = c.g1_mul(c.G1_GEN, 31337)
    assert not c.pairing_check([(c.G2_GEN, c.g1_neg(p1)),
                                (c.g2_mul(c.G2_GEN, 2), c.G1_GEN)])


def test_native_agrees_with_python_backend():
    """The exact same pairing_check answer with and without the native lib."""
    p1 = c.g1_mul(c.G1_GEN, 777)
    q2 = c.g2_mul(c.G2_GEN, 777)
    pairs = [(c.G2_GEN, c.g1_neg(p1)), (q2, c.G1_GEN)]
    native = c.pairing_check(pairs)
    python = c.multi_pairing(pairs) == c.F12_ONE
    assert native == python == True      # noqa: E712


def test_subgroup_check_differential():
    assert c.g2_in_subgroup(c.G2_GEN)
    assert c.g2_in_subgroup(c.g2_mul(c.G2_GEN, 12345))


def test_infinity_handling():
    assert c.g1_mul(c.G1_GEN, c.R) is None
    assert c.g2_mul(c.G2_GEN, c.R) is None
    assert c.pairing_check([(c.G2_GEN, None), (None, c.G1_GEN)])


# --- the COMMIT-set check on the library's own thread (PR 48) --------------
#
# `commit_check_begin` / `commit_check_end` against the Python twin,
# crypto/bls.py `_combined_pairs` + `multi_pairing`, under the SAME
# coefficients: the native side draws none.

from plenum_tpu.crypto import bls  # noqa: E402

_MSG = b"the ordered batch's value"


def _commit_set(n, forged=()):
    """n decoded (signature, message, key) triples over one message; the
    members in `forged` signed something else."""
    keys = [bls.BlsSignKey(seed=bytes([0xC0 + i]) * 32) for i in range(n)]
    return [(bls._decode_sig(k.sign(b"another value" if i in forged
                                    else _MSG)), _MSG,
             bls._decode_vk(k.verkey)) for i, k in enumerate(keys)]


def _twin(entries, coeffs) -> bool:
    return c.multi_pairing(bls._combined_pairs(entries, coeffs)) == c.F12_ONE


def _native(entries, coeffs):
    ticket = c.commit_check_begin(
        [s for s, _, _ in entries], [k for _, _, k in entries], coeffs,
        c.hash_to_g1(_MSG, bls._MSG_DOMAIN))
    assert ticket
    verdict, seconds = c.commit_check_end(ticket)
    assert seconds > 0
    return verdict


@pytest.mark.parametrize("n, forged, expect", [
    (1, (), True), (3, (), True), (4, (), True),
    (1, (0,), False), (3, (1,), False), (4, (3,), False),
])
def test_commit_check_against_the_python_twin(n, forged, expect):
    entries = _commit_set(n, forged)
    coeffs = bls.batch_coefficients(n)
    assert _twin(entries, coeffs) is expect
    assert _native(entries, coeffs) is expect


def test_commit_check_uses_the_coefficients_it_is_given():
    """A doctored pair (s1 + d, s2 - d) cancels under equal coefficients
    and under no others: both engines accept it under (1, 1) and refuse it
    under fresh ones, so the native side combines with what it was
    handed."""
    (s1, m, k1), (s2, _, k2) = _commit_set(2)
    delta = c.g1_mul(c.G1_GEN, 424242)
    entries = [(c.g1_add(s1, delta), m, k1),
               (c.g1_add(s2, c.g1_neg(delta)), m, k2)]
    assert _twin(entries, [1, 1]) and _native(entries, [1, 1])
    fresh = bls.batch_coefficients(2)
    assert not _twin(entries, fresh) and not _native(entries, fresh)


def test_commit_check_malformed_point_gives_no_verdict():
    (sig, _, key), = _commit_set(1)
    off_curve = (sig[0], (sig[1] + 1) % c.P)
    assert _native([(off_curve, _MSG, key)], [3]) is None


def test_commit_check_ticket_is_ended_once_and_polled_without_blocking():
    entries = _commit_set(3)
    ticket = c.commit_check_begin(
        [s for s, _, _ in entries], [k for _, _, k in entries],
        bls.batch_coefficients(3), c.hash_to_g1(_MSG, bls._MSG_DOMAIN))
    polled = c.commit_check_end(ticket, wait=False)
    while polled is None:               # still running: a poll returns
        polled = c.commit_check_end(ticket, wait=False)
    assert polled[0] is True
    assert c.commit_check_end(ticket) == (None, 0.0)    # handed out once


def test_commit_check_from_two_python_threads():
    """More askers than the one worker, a short switch interval: every
    ticket comes back with its own set's verdict."""
    import sys
    import threading
    good, bad = _commit_set(3), _commit_set(3, forged=(2,))
    wrong: list = []

    def ask(entries, expect):
        for _ in range(6):
            if _native(entries, bls.batch_coefficients(3)) is not expect:
                wrong.append(expect)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask, args=a)
                   for a in ((good, True), (bad, False)) * 2]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []


def test_commit_check_after_fork():
    """A forked child has none of its parent's threads: a ticket of the
    parent's gets no verdict there (the caller's Python twin decides),
    and the child's own check runs on a worker of its own."""
    import os
    entries = _commit_set(3)
    coeffs = bls.batch_coefficients(3)
    h = c.hash_to_g1(_MSG, bls._MSG_DOMAIN)
    args = ([s for s, _, _ in entries], [k for _, _, k in entries], coeffs, h)
    assert _native(entries, coeffs) is True          # the worker exists
    parents = c.commit_check_begin(*args)
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:                                     # the child
        try:
            inherited = c.commit_check_end(parents)
            own = c.commit_check_end(c.commit_check_begin(*args))
            os.write(w, repr((inherited[0], own[0])).encode())
        finally:
            os._exit(0)
    os.close(w)
    try:
        assert c.commit_check_end(parents)[0] is True
        got = b""
        while chunk := os.read(r, 64):
            got += chunk
    finally:
        os.close(r)
        os.waitpid(pid, 0)
    assert got == b"(None, True)"
