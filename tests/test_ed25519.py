"""Ed25519 kernel + provider tests: field/point unit checks, RFC 8032 vectors,
random signatures from the C library, adversarial inputs."""
import hashlib
import os
import random

import numpy as np
import pytest

import jax.numpy as jnp

from plenum_tpu.ops import ed25519 as ops
from plenum_tpu.crypto.ed25519 import (Ed25519Signer, CpuEd25519Verifier,
                                       JaxEd25519Verifier, make_verifier)
from plenum_tpu.utils.base58 import b58encode, b58decode


# --- field arithmetic vs python ints --------------------------------------

def _rand_fe(rng):
    return rng.randrange(ops.P)


def test_limb_roundtrip():
    rng = random.Random(0)
    for _ in range(20):
        x = _rand_fe(rng)
        assert ops.limbs_to_int(ops.int_to_limbs(x)) == x


@pytest.mark.parametrize("op,pyop", [
    ("add", lambda a, b: (a + b) % ops.P),
    ("sub", lambda a, b: (a - b) % ops.P),
    ("mul", lambda a, b: (a * b) % ops.P),
])
def test_field_ops_match_bigint(op, pyop):
    rng = random.Random(1)
    fn = {"add": ops.f_add, "sub": ops.f_sub, "mul": ops.f_mul}[op]
    xs = [_rand_fe(rng) for _ in range(8)] + [0, 1, ops.P - 1, ops.P - 19]
    ys = [_rand_fe(rng) for _ in range(8)] + [ops.P - 1, 0, ops.P - 1, 19]
    a = jnp.asarray(np.stack([ops.int_to_limbs(x) for x in xs]))
    b = jnp.asarray(np.stack([ops.int_to_limbs(y) for y in ys]))
    # outputs are in CARRIED form (congruent mod p, limbs possibly signed);
    # canonicalize before comparing against the bigint reference
    out = ops.f_canon(fn(a, b))
    for i, (x, y) in enumerate(zip(xs, ys)):
        assert ops.limbs_to_int(np.asarray(out)[i]) == pyop(x, y), (op, i)


def test_f_canon():
    # a value deliberately left ≥ p
    x = ops.P + 12345
    l = jnp.asarray(ops.int_to_limbs(x % (1 << 260))[None, :])
    c = np.asarray(ops.f_canon(l))[0]
    assert ops.limbs_to_int(c) == 12345
    assert all(0 <= v <= ops.MASK for v in c)


# --- point ops vs python reference ----------------------------------------

def _py_edwards_add(p1, p2):
    x1, y1 = p1
    x2, y2 = p2
    den = ops.D * x1 * x2 * y1 * y2 % ops.P
    x3 = (x1 * y2 + x2 * y1) * pow(1 + den, ops.P - 2, ops.P) % ops.P
    y3 = (y1 * y2 + x1 * x2) * pow(1 - den, ops.P - 2, ops.P) % ops.P
    return (x3, y3)


def _to_affine(pt):
    x, y, z, _ = (ops.limbs_to_int(np.asarray(c)[0]) for c in pt)
    zi = pow(z, ops.P - 2, ops.P)
    return (x * zi % ops.P, y * zi % ops.P)


def _dev_pt(affine):
    return tuple(jnp.asarray(v) for v in ops.points_to_limbs([affine]))


def test_pt_add_and_double_match_reference():
    B = (ops.BX, ops.BY)
    b_dev = _dev_pt(B)
    two_b = ops.pt_double(b_dev)
    assert _to_affine(two_b) == _py_edwards_add(B, B)
    three_b = ops.pt_add(two_b, b_dev)
    assert _to_affine(three_b) == _py_edwards_add(_py_edwards_add(B, B), B)
    # unified add used as doubling agrees with dedicated double
    assert _to_affine(ops.pt_add(b_dev, b_dev)) == _to_affine(ops.pt_double(b_dev))


def test_pt_add_identity():
    B = (ops.BX, ops.BY)
    b_dev = _dev_pt(B)
    o = _dev_pt((0, 1))
    assert _to_affine(ops.pt_add(b_dev, o)) == B
    assert _to_affine(ops.pt_add(o, b_dev)) == B


# --- RFC 8032 test vectors ------------------------------------------------

RFC8032_VECTORS = [
    # (secret_seed_hex, public_hex, message_hex, signature_hex) — §7.1
    ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
     "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
     "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
     "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
     "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
     "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
     "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
    ("c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
     "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
     "af82",
     "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
     "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"),
]


@pytest.mark.parametrize("case", range(len(RFC8032_VECTORS)))
def test_rfc8032_vectors(case):
    seed_h, pub_h, msg_h, sig_h = RFC8032_VECTORS[case]
    msg = bytes.fromhex(msg_h)
    sig = bytes.fromhex(sig_h)
    vk = bytes.fromhex(pub_h)
    # signer reproduces the vector
    s = Ed25519Signer(bytes.fromhex(seed_h))
    assert s.verkey == vk
    assert s.sign(msg) == sig
    # both verifier backends accept
    for backend in ("cpu", "jax"):
        v = make_verifier(backend)
        assert v.verify(msg, sig, vk), backend
        assert not v.verify(msg + b"x", sig, vk), backend
        bad = bytearray(sig); bad[0] ^= 1
        assert not v.verify(msg, bytes(bad), vk), backend


# --- random batch vs C library -------------------------------------------

def test_jax_batch_matches_cpu_on_mixed_batch():
    rng = random.Random(3)
    signers = [Ed25519Signer(bytes([i]) * 32) for i in range(4)]
    items = []
    expect = []
    for i in range(37):
        s = signers[i % 4]
        msg = rng.randbytes(rng.randint(0, 100))
        sig = s.sign(msg)
        good = rng.random() < 0.7
        if not good:
            kind = rng.randrange(4)
            if kind == 0:
                b = bytearray(sig); b[rng.randrange(64)] ^= 0xFF; sig = bytes(b)
            elif kind == 1:
                msg = msg + b"!"
            elif kind == 2:
                sig = sig[:32] + (ops.L + 5).to_bytes(32, "little")  # S >= L
            else:
                sig = b"\xff" * 64  # garbage R
        items.append((msg, sig, s.verkey))
        expect.append(good)
    cpu = CpuEd25519Verifier().verify_batch(items)
    dev = JaxEd25519Verifier().verify_batch(items)
    assert list(cpu) == expect
    assert list(dev) == expect


def test_malformed_inputs_never_raise():
    v = JaxEd25519Verifier()
    items = [(b"m", b"short", b"\x00" * 32),
             (b"m", b"\x00" * 64, b"bad"),
             (b"m", b"\x00" * 64, b"\x00" * 32),
             (b"", b"\xff" * 64, b"\xff" * 32)]
    out = v.verify_batch(items)
    assert not out.any()
    assert CpuEd25519Verifier().verify_batch(items).any() == False


def test_verkey_cache_hits():
    s = Ed25519Signer(b"\x07" * 32)
    v = JaxEd25519Verifier()
    msgs = [b"m%d" % i for i in range(8)]
    items = [(m, s.sign(m), s.verkey) for m in msgs]
    assert v.verify_batch(items).all()
    # the compressed dispatch ships raw key bytes and decompresses on
    # device: the host-side point cache is never populated on the hot path
    assert len(v._pt_cache) == 0

    class _Limb(JaxEd25519Verifier):
        _compressed_dispatch = False

    lv = _Limb()
    assert lv.verify_batch(items).all()
    assert len(lv._pt_cache) == 1      # limb path still caches per verkey


# --- base58 ---------------------------------------------------------------

def test_base58_roundtrip():
    rng = random.Random(5)
    for _ in range(20):
        data = rng.randbytes(rng.randint(0, 40))
        assert b58decode(b58encode(data)) == data
    assert b58encode(b"\x00\x00a") .startswith("11")
    with pytest.raises(ValueError):
        b58decode("0OIl")


# --- backend-agreement regression tests (review round 3) ------------------

def test_backends_agree_on_noncanonical_encodings():
    """Non-canonical point encodings (y >= p) must be rejected by BOTH
    backends — a backend verdict split would fork the pool."""
    bad_vk = (ops.P + 1).to_bytes(32, "little")
    sig = bad_vk + (0).to_bytes(32, "little")
    for backend in ("cpu", "jax"):
        v = make_verifier(backend)
        assert not v.verify(b"msg", sig, bad_vk), backend
    # canonical-but-valid still passes both
    s = Ed25519Signer(b"\x09" * 32)
    m = b"agree"
    for backend in ("cpu", "jax"):
        assert make_verifier(backend).verify(m, s.sign(m), s.verkey), backend


def test_non_bytes_items_return_false_not_raise():
    for backend in ("cpu", "jax"):
        v = make_verifier(backend)
        out = v.verify_batch([("str-msg", "s" * 64, b"\x00" * 32),
                              (b"m", None, b"\x00" * 32)])
        assert not out.any(), backend


def test_pt_cache_bounded():
    v = JaxEd25519Verifier(cache_size=4)
    for i in range(10):
        v._decompress_cached(i.to_bytes(32, "little"))
    assert len(v._pt_cache) == 4


# --- compressed dispatch: device-side key decompression (round 5) ---------

def test_decompress_kernel_matches_host():
    """Device decompression must agree with the host `decompress` twin on
    valid keys (producing the same -A quarter points as ext_quarters) and
    on every adversarial encoding class."""
    keys = [Ed25519Signer(bytes([i + 40]) * 32).verkey for i in range(3)]
    bad = [
        (ops.P + 1).to_bytes(32, "little"),          # y >= p (non-canonical)
        (ops.P - 1).to_bytes(32, "little"),          # y = p-1: off curve?
        bytes(32),                                   # y = 0
        (1 | (1 << 255)).to_bytes(32, "little"),     # y = 1 -> x = 0, sign=1
        (2).to_bytes(32, "little"),                  # y = 2
    ]
    all_keys = keys + bad
    k_u8 = np.frombuffer(b"".join(all_keys), np.uint8).reshape(-1, 32)
    import jax.numpy as jnp
    (qx, qy, qz, qt), valid = ops.decompress_kernel(jnp.asarray(k_u8))
    valid = np.asarray(valid)
    for i, kb in enumerate(all_keys):
        host = ops.decompress(kb)
        assert valid[i] == (host is not None), (i, kb.hex())
        if host is None:
            continue
        neg = ((ops.P - host[0]) % ops.P, host[1])
        want = ops.ext_quarters(neg)                 # [4, 4, NLIMB]
        for q in range(4):
            got = [np.asarray(c)[q, i] for c in (qx, qy, qz, qt)]
            x, y, z, t = (ops.limbs_to_int(np.asarray(ops.f_canon(
                jnp.asarray(g[None, :])))[0]) for g in got)
            zi = pow(z, ops.P - 2, ops.P)
            wx = ops.limbs_to_int(want[q, 0])
            wy = ops.limbs_to_int(want[q, 1])
            wz = ops.limbs_to_int(want[q, 2])
            wzi = pow(wz, ops.P - 2, ops.P)
            assert x * zi % ops.P == wx * wzi % ops.P, (i, q)
            assert y * zi % ops.P == wy * wzi % ops.P, (i, q)
            # extended-coordinate invariant: T = X*Y/Z
            assert t % ops.P == x * y % ops.P * zi % ops.P, (i, q)


def test_bytes_and_limb_dispatch_agree():
    """The compressed byte dispatch and the limb-staged dispatch are the
    same verifier semantics — run both on a mixed batch and compare."""
    rng = random.Random(11)
    signers = [Ed25519Signer(bytes([i + 60]) * 32) for i in range(3)]
    items = []
    for i in range(19):
        s = signers[i % 3]
        msg = rng.randbytes(20)
        sig = s.sign(msg)
        if i % 4 == 0:
            b = bytearray(sig); b[1] ^= 0x55; sig = bytes(b)
        if i % 7 == 0:
            sig = sig[:32] + (ops.L + i).to_bytes(32, "little")  # S >= L
        items.append((msg, sig, s.verkey))
    items.append((b"m", b"\x01" * 64, bytes(32)))      # y=0 verkey
    items.append((b"m", b"\x01" * 64, (ops.P + 2).to_bytes(32, "little")))

    class _Limb(JaxEd25519Verifier):
        _compressed_dispatch = False

    got_b = JaxEd25519Verifier().verify_batch(items)
    got_l = _Limb().verify_batch(items)
    cpu = CpuEd25519Verifier().verify_batch(items)
    assert list(got_b) == list(got_l) == list(cpu)
