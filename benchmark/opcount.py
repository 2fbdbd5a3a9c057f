"""Operations and bytes one verified ed25519 signature needs.

Counted from the algorithm the kernel states (ops/ed25519.py's
docstring), never from its code: whatever implements the same
algorithm is held to the same count, and work beyond it (padding
lanes, wider selects, a slower field form) shows as a lower share.

  field        GF(2^255-19) in 20 limbs of 13 bits, int32 lanes
  decompress   one shared square-root chain over A and R
  hash         SHA-512 over R || A || M, M padded to the message cap
  ladder       [S]B + [L-h]A, Straus, 64 windows of 4 bits: 4 doublings,
               one add from the per-lane 16-entry A table (built with
               15 additions), one mixed add from the constant B table,
               16-way branch-free selects of both
  final        subtract R, multiply by the cofactor 8, compare with
               the identity

An operation is one int32 add, subtract, multiply, shift, logic
operation or select on one lane. ``int32_ops`` and ``hbm_bytes`` take
the message cap and the signature count and nothing else.
"""

from __future__ import annotations

LIMBS = 20

# one field operation, in int32 operations on a lane
FE_ADD = LIMBS  # limb-wise, carries deferred
FE_CARRY = 3 * LIMBS  # shift, mask, add a limb
# schoolbook product: 400 multiplies, 361 adds into 39 columns, the 19
# high columns folded with a multiply and an add each, one carry pass
FE_MUL = LIMBS * LIMBS + (LIMBS * LIMBS - (2 * LIMBS - 1)) + 2 * (LIMBS - 1) + FE_CARRY
# square: 210 distinct products, 190 of them doubled, 171 adds into the
# columns, fold and carry as above
FE_SQ = (
    LIMBS * (LIMBS + 1) // 2
    + LIMBS * (LIMBS - 1) // 2
    + (LIMBS * (LIMBS + 1) // 2 - (2 * LIMBS - 1))
    + 2 * (LIMBS - 1)
    + FE_CARRY
)

# point operations on extended twisted Edwards coordinates, as
# (multiplies, squarings, field additions)
DOUBLE = (3, 4, 6)  # dbl-2008-hwcd without T
DOUBLE_T = (4, 4, 6)  # the window's last doubling feeds an addition
ADD_CACHED = (8, 0, 6)  # add-2008-hwcd-3, addend in cached form
ADD_MIXED = (6, 0, 6)  # addend affine and cached (Z = 1), T not needed
ADD_FULL = (9, 0, 7)  # both in extended form: the A table's build
TO_CACHED = (1, 0, 2)  # Y+X, Y-X, 2dT

WINDOWS = 64
TABLE = 16
SQRT_CHAIN = (12 + 8, 252)  # x^((p-5)/8) by an addition chain, u/v set-up and the check

SHA512_ROUND = (
    # 64-bit words as two int32 halves: a rotate is 4 (2 shifts, 2 ors
    # on each... counted 4), a logic operation 2, an add 3 (two adds
    # and the carry)
    4 * 6  # Sigma0, Sigma1
    + 2 * 4  # their xors
    + 2 * 3  # Ch
    + 2 * 4  # Maj
    + 3 * 7  # t1, t2 and the two state adds
    + 4 * 4 + 2 * 2 + 2 * 4  # sigma0, sigma1 of the schedule: rotates, shifts, xors
    + 3 * 3  # the schedule's adds
)
SHA512_BLOCK = 80 * SHA512_ROUND + 8 * 3


def _point(op) -> int:
    m, s, a = op
    return m * FE_MUL + s * FE_SQ + a * FE_ADD


def sha512_blocks(cap: int) -> int:
    """Blocks of SHA-512 over R || A || M with M padded to ``cap``."""
    return (64 + cap + 17 + 127) // 128


def int32_ops(cap: int, signatures: int) -> int:
    decompress = 2 * (SQRT_CHAIN[0] * FE_MUL + SQRT_CHAIN[1] * FE_SQ)
    sha = sha512_blocks(cap) * SHA512_BLOCK
    scalars = 2 * 64 * 4 + 40 * LIMBS  # the window digits; h mod L, L - h, S < L
    table = (TABLE - 1) * (_point(ADD_FULL) + _point(TO_CACHED))
    selects = (TABLE - 1) * LIMBS * (4 + 3)  # a cached point, an affine cached point
    window = (
        3 * _point(DOUBLE) + _point(DOUBLE_T)
        + _point(ADD_CACHED) + _point(ADD_MIXED) + selects
    )
    final = _point(ADD_FULL) + 3 * _point(DOUBLE) + 4 * FE_CARRY
    per_sig = decompress + sha + scalars + table + WINDOWS * window + final
    return per_sig * signatures


def hbm_bytes(cap: int, signatures: int) -> int:
    """Message, its length, key, R and S in; one verdict out."""
    return (cap + 4 + 32 + 32 + 32 + 1) * signatures
