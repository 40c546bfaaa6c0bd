"""Operations and bytes of one Ed25519 verification in
plenum_tpu/ops/ed25519.verify_kernel_bytes, from its shapes.

The kernel works on radix-2^13 limbs, 20 to a field element, in int32. One
field multiplication is a 20 x 20 schoolbook product (400 multiplies and
400 adds) plus the fold of the high limbs and two carry passes (~120 ops):
~920 int32 operations. Verification is one double-scalar multiplication
[s]B - [h]A over 253 bits: 253 doublings (4 squarings + 4 multiplications
each, extended coordinates) and, with 4-bit windows on both scalars, ~127
additions (8 multiplications each), plus one decompression of A and of R
(a ~265-multiplication exponentiation each). These are counts of what the
algorithm needs, not of what XLA emitted."""
from __future__ import annotations

VERIFY_PROGRAM = "verify_kernel"    # substring of the program's trace name
FIELD_MUL_OPS = 920
POINT_DOUBLE_MULS = 8
POINT_ADD_MULS = 8
DECOMPRESS_MULS = 265


def field_muls_per_sig() -> int:
    return 253 * POINT_DOUBLE_MULS + 127 * POINT_ADD_MULS \
        + 2 * DECOMPRESS_MULS


def ops_per_sig() -> int:
    """int32 operations one verification needs (SHA-512 of the message,
    done on the host, is not in the kernel)."""
    return field_muls_per_sig() * FIELD_MUL_OPS


def bytes_per_sig(lanes: int, keys: int = 64) -> float:
    """HBM bytes one lane moves at least: its signature (64), its digest
    scalar (32), its key index (4), its verdict (1), and its share of the
    key table (32 B a key) read once per execution."""
    return 64 + 32 + 4 + 1 + 32.0 * keys / lanes


def lanes_per_execution(config: dict) -> int:
    """Padded lanes of one dispatch: the deployment's smallest pinned
    bucket (waves are padded up to it)."""
    return int(config["shapes"][0][0])
