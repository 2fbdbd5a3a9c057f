"""GF(2^255 - 19) field arithmetic for TPU, vectorized over batch lanes.

Design notes (TPU-first, not a port):

* TPU has no 64-bit integers and no big-int unit. A field element is a
  **tuple of ``NLIMBS = 20`` separate int32 arrays** (one per 13-bit
  limb), each shaped ``(N...)`` with the batch on the trailing axes.
  The tuple-of-arrays form (rather than one stacked ``(20, N)`` array)
  is the load-bearing choice: every field op is then a pure elementwise
  DAG over same-shaped vectors with **zero data-movement ops** — no
  stack/concatenate/roll — which XLA fuses into a handful of kernels.
  The previous stacked layout made each multiply materialize its
  (41, N) intermediates through HBM (concatenate/stack are fusion
  breakers), leaving the verify kernel ~25x slower than its ALU cost.
* 13-bit limbs are the sweet spot for int32 lanes: a full schoolbook
  product limb is a sum of 20 partial products each < 2^26, total < 2^31,
  so the whole convolution accumulates in plain int32 with no carries
  inside the inner loop.
* Limbs are kept **nonnegative end-to-end**: subtraction adds a
  per-limb-large multiple of p (``_BIAS``, limbs in [12288, 20479],
  value ≡ 0 mod p) before subtracting, so borrows never ripple and a
  negative carry can never silently fall off the top headroom limb of
  the multiply pipeline. Carry propagation is then monotone and
  converges in a fixed 2-3 rounds (floor-semantics shifts + ``& MASK``).
* Reduction is lazy. ``carry()`` folds the carry-out of limb 19 back into
  limb 0 multiplied by ``WRAP = 2^260 mod p = 608``. Elements stay in a
  redundant range; exact canonical comparisons are done by
  ``canonical()`` / ``is_zero()`` without a full freeze-subtract.
* Everything is static-shaped, static-control-flow jnp code; the hot
  loops live in :mod:`cometbft_tpu.ops.ed25519`. ``stack``/``unstack``
  convert to/from the (20, N) array form at module boundaries (tests,
  the scalar module, byte IO).

Reference seams replaced (behavioral parity targets, not code ports):
the curve25519-voi field element used by the reference's
``crypto/ed25519/ed25519.go`` verify paths.
"""

from __future__ import annotations

import os

import numpy as np
import jax.numpy as jnp
from jax import lax

NLIMBS = 20
LIMB_BITS = 13
MASK = (1 << LIMB_BITS) - 1
P = 2**255 - 19
WRAP = (1 << (NLIMBS * LIMB_BITS)) % P  # 2^260 mod p == 608


# --- compact (rolled) mode ---------------------------------------------
#
# The tuple-of-limbs convolution unrolls to ~1.4k HLO ops per multiply —
# ideal for the TPU backend (pure fusable elementwise DAG) but fatal for
# the XLA *CPU* backend, whose compile time explodes superlinearly on
# the verify kernel's op count (>80 min / OOM at any width and any opt
# level; docs/PERF.md "CPU-backend compile pathology"). Compact mode
# expresses the SAME arithmetic rolled: stacked (nlimbs, N...) arrays, a
# lax.scan over the 20 partial-product rows, and whole-vector carry
# rounds — ~70 HLO ops per multiply, which the CPU backend compiles in
# seconds. Value-identical by construction (same partial products, same
# carry schedule); differential tests cross-check both forms.
#
# Mode selection is per-process: explicitly via set_compact()/env
# GRAFT_COMPACT_FIELD, else automatic — compact exactly on the CPU
# backend (the virtual-mesh dryrun, CPU test lanes, entry()'s CPU
# compile check), tuple form on real accelerators.

_COMPACT = None  # True/False forced, None = auto


def set_compact(v) -> None:
    """Force compact mode on/off (tests); None restores auto."""
    global _COMPACT
    _COMPACT = v


def compact_mode() -> bool:
    if _COMPACT is not None:
        return _COMPACT
    env = os.environ.get("GRAFT_COMPACT_FIELD")
    if env is not None:
        return env == "1"
    from ..utils import device

    return device.on_cpu()


def to_limbs(x: int) -> np.ndarray:
    """Host: python int -> canonical 20-limb int32 vector (value mod p)."""
    return raw_limbs(x % P)


def raw_limbs(x: int) -> np.ndarray:
    """Host: python int -> 20-limb vector WITHOUT reduction (x < 2^260)."""
    assert 0 <= x < 1 << (NLIMBS * LIMB_BITS)
    out = np.zeros(NLIMBS, np.int32)
    for i in range(NLIMBS):
        out[i] = x & MASK
        x >>= LIMB_BITS
    return out


def from_limbs(limbs) -> int:
    """Host/test: one limb vector (any redundancy, signed) -> int mod p."""
    arr = np.asarray(limbs, dtype=np.int64)
    val = 0
    for i in reversed(range(arr.shape[0])):
        val = (val << LIMB_BITS) + int(arr[i])
    return val % P


# --- representation adapters -------------------------------------------


def stack(t):
    """tuple-of-limbs -> one (20, N...) int32 array (module boundary)."""
    shape = jnp.broadcast_shapes(*(jnp.shape(x) for x in t))
    return jnp.stack(
        [jnp.broadcast_to(x, shape).astype(jnp.int32) for x in t], axis=0
    )


def unstack(arr):
    """(20, N...) array -> tuple-of-limbs."""
    return tuple(arr[i] for i in range(NLIMBS))


def unstack_n(arr, n: int):
    """(n, N...) array -> n-tuple (scalar module's variable widths)."""
    return tuple(arr[i] for i in range(n))


def zero(shape=()):
    z = jnp.zeros(shape, jnp.int32)
    return (z,) * NLIMBS


def const(x: int):
    """Device constant: tuple of int32 scalars (broadcasts everywhere)."""
    return tuple(jnp.int32(int(v)) for v in to_limbs(x))


def _bshape(*args):
    return jnp.broadcast_shapes(*(jnp.shape(a[0]) for a in args))


def _carry_stacked(x, rounds: int, wrap: bool):
    """Stacked-array carry rounds (compact mode): x is (n, N...) int32.

    wrap=True folds the top limb's carry into limb 0 times WRAP (the
    20-limb field carry); wrap=False drops it (callers guarantee a zero
    headroom limb, same contract as the tuple _carry_noWrap)."""

    def rnd(x):
        c = lax.shift_right_arithmetic(x, LIMB_BITS)
        r = jnp.bitwise_and(x, MASK)
        up = jnp.concatenate(
            [c[-1:] * WRAP if wrap else jnp.zeros_like(c[-1:]), c[:-1]],
            axis=0,
        )
        return r + up

    if rounds > 4:  # long chains (scalar folds) roll the rounds too
        return lax.fori_loop(0, rounds, lambda _, v: rnd(v), x)
    for _ in range(rounds):
        x = rnd(x)
    return x


def carry(x, rounds: int = 3):
    """Propagate carries; carry-out of limb 19 wraps to limb 0 times WRAP.

    Preserves the value mod p. With inputs bounded by 2^31 the default 3
    rounds bring limbs into (-2^13, 2^13 + WRAP]; pure per-limb
    elementwise ops, the cross-limb shift is just tuple reindexing."""
    if compact_mode():
        return unstack(_carry_stacked(stack(x), rounds, wrap=True))
    for _ in range(rounds):
        c = tuple(lax.shift_right_arithmetic(v, LIMB_BITS) for v in x)
        r = tuple(jnp.bitwise_and(v, MASK) for v in x)
        x = (r[0] + c[NLIMBS - 1] * WRAP,) + tuple(
            r[i] + c[i - 1] for i in range(1, NLIMBS)
        )
    return x


def _make_bias() -> np.ndarray:
    """A multiple of p whose every limb is in [12288, 20479]: added before
    subtraction so limb values stay nonnegative (see module docstring)."""
    base = np.full(NLIMBS, 12288, np.int64)
    v = sum(int(b) << (LIMB_BITS * i) for i, b in enumerate(base)) % P
    adj = to_limbs((-v) % P).astype(np.int64)
    out = base + adj
    assert (out >= 12288).all() and (out <= 20479).all()
    return out.astype(np.int32)


_BIAS = tuple(int(v) for v in _make_bias())


def add(a, b):
    return carry(tuple(x + y for x, y in zip(a, b)), 1)


def sub(a, b):
    """a - b mod p; bias keeps limbs nonneg (inputs must be carried)."""
    return carry(
        tuple(x + k - y for x, y, k in zip(a, b, _BIAS)), 2
    )


def neg(a):
    return carry(tuple(k - x for x, k in zip(a, _BIAS)), 2)


def _conv_mul(a, b):
    """Schoolbook 20x20 limb convolution -> 41-limb tuple.

    Output-stationary: each result limb is an independent sum of <= 20
    lane-wise products — a pure fusable elementwise expression.

    The convolution proper spans limbs 0..38; limbs 39-40 are headroom
    for the carry rounds (limb 38 can carry ~2^13.5 into limb 39, which
    can carry 1 into limb 40 — dropping that bit would lose
    2^520 ≡ WRAP^2)."""
    outs = []
    for k in range(2 * NLIMBS - 1):
        lo = max(0, k - NLIMBS + 1)
        hi = min(NLIMBS - 1, k)
        s = a[lo] * b[k - lo]
        for i in range(lo + 1, hi + 1):
            s = s + a[i] * b[k - i]
        outs.append(s)
    z = jnp.zeros_like(outs[0])
    outs.append(z)  # limb 39 headroom
    outs.append(z)  # limb 40 headroom
    return tuple(outs)


def _carry_noWrap(c, rounds: int = 3):
    n = len(c)
    for _ in range(rounds):
        cc = tuple(lax.shift_right_arithmetic(v, LIMB_BITS) for v in c)
        r = tuple(jnp.bitwise_and(v, MASK) for v in c)
        c = (r[0],) + tuple(r[i] + cc[i - 1] for i in range(1, n))
    return c


def _reduce_41(c):
    """41-limb convolution output -> carried 20-limb element.

    Round counts are tight by bound analysis (checked by the exact
    differential fuzz chains in tests/test_fe25519.py): conv limbs
    < 2^31 -> one noWrap round leaves carries <= 2^18, a second leaves
    limbs <= MASK + 2^5; the fold term hi*WRAP <= 2^22.6, and two wrap
    rounds bring limbs back under MASK + WRAP + 2^5 — the same
    "carried" contract the convolutions assume (products then stay
    under 2^31: carried limbs < 2^13.2, so each of the <= 20 partial
    products is < 2^26.4 and their sum < 2^30.8)."""
    c = _carry_noWrap(c, 2)
    lo = c[:NLIMBS]
    hi = c[NLIMBS : 2 * NLIMBS]
    out = [x + y * WRAP for x, y in zip(lo, hi)]
    out[0] = out[0] + c[2 * NLIMBS] * (WRAP * WRAP)
    return carry(tuple(out), 2)


def _mul_compact(a, b):
    """Compact-mode multiply: the same 20x20 schoolbook convolution as
    _conv_mul/_reduce_41, rolled into a 20-step lax.scan over stacked
    limbs (value-identical partial products and carry schedule, ~20x
    smaller HLO — see the compact-mode note at the top)."""
    A, B = stack(a), stack(b)
    sh = jnp.broadcast_shapes(A.shape[1:], B.shape[1:])

    def _bcast(x):  # align batch dims from the right (scalar consts)
        pad = len(sh) - (x.ndim - 1)
        x = x.reshape((NLIMBS,) + (1,) * pad + x.shape[1:])
        return jnp.broadcast_to(x, (NLIMBS,) + sh).astype(jnp.int32)

    A, B = _bcast(A), _bcast(B)
    acc0 = jnp.zeros((2 * NLIMBS + 1,) + sh, jnp.int32)

    def body(acc, i):
        contrib = lax.dynamic_index_in_dim(A, i, 0, keepdims=False) * B
        seg = lax.dynamic_slice_in_dim(acc, i, NLIMBS, axis=0)
        return (
            lax.dynamic_update_slice_in_dim(acc, seg + contrib, i, axis=0),
            None,
        )

    acc, _ = lax.scan(body, acc0, jnp.arange(NLIMBS))
    # stacked _reduce_41: two no-wrap rounds, fold, two wrap rounds
    acc = _carry_stacked(acc, 2, wrap=False)
    out = acc[:NLIMBS] + acc[NLIMBS : 2 * NLIMBS] * WRAP
    out = out.at[0].add(acc[2 * NLIMBS] * (WRAP * WRAP))
    return unstack(_carry_stacked(out, 2, wrap=True))


def mul(a, b):
    """Field multiply. Inputs must be carried (|limb| <~ 2^13.3)."""
    if compact_mode():
        return _mul_compact(a, b)
    return _reduce_41(_conv_mul(a, b))


def square(a):
    """Field square via the general convolution.

    MEASURED: the symmetric convolution (fewer multiplies: ~110 vs 400)
    is ~30% SLOWER end-to-end on v5e (47.9ms vs 36.6ms @8192 lanes for
    the full verify kernel) — the doubled-cross expression tree
    schedules worse than the regular output-stationary conv, and the
    VPU is not multiply-bound here. Keep the general conv.
    """
    if compact_mode():
        return _mul_compact(a, a)
    return _reduce_41(_conv_mul(a, a))


def mul_scalar(a, k: int):
    """Multiply by a small nonneg python int (k < 2^17)."""
    return carry(tuple(v * jnp.int32(k) for v in a), 3)


def sqn(x, n: int):
    """x^(2^n) via n squarings; fori_loop keeps the HLO small."""
    if n <= 4:
        for _ in range(n):
            x = square(x)
        return x
    return lax.fori_loop(0, n, lambda _, v: square(v), x)


def pow2523(x):
    """x^((p-5)/8) = x^(2^252 - 3). Standard curve25519 addition chain."""
    x2 = square(x)                 # 2
    x4 = square(x2)                # 4
    x8 = square(x4)                # 8
    x9 = mul(x8, x)                # 9
    x11 = mul(x9, x2)              # 11
    x22 = square(x11)              # 22
    x_5_0 = mul(x22, x9)           # 2^5 - 1 = 31
    x_10_5 = sqn(x_5_0, 5)
    x_10_0 = mul(x_10_5, x_5_0)    # 2^10 - 1
    x_20_10 = sqn(x_10_0, 10)
    x_20_0 = mul(x_20_10, x_10_0)  # 2^20 - 1
    x_40_20 = sqn(x_20_0, 20)
    x_40_0 = mul(x_40_20, x_20_0)  # 2^40 - 1
    x_50_10 = sqn(x_40_0, 10)
    x_50_0 = mul(x_50_10, x_10_0)  # 2^50 - 1
    x_100_50 = sqn(x_50_0, 50)
    x_100_0 = mul(x_100_50, x_50_0)    # 2^100 - 1
    x_200_100 = sqn(x_100_0, 100)
    x_200_0 = mul(x_200_100, x_100_0)  # 2^200 - 1
    x_250_50 = sqn(x_200_0, 50)
    x_250_0 = mul(x_250_50, x_50_0)    # 2^250 - 1
    x_252_2 = sqn(x_250_0, 2)
    return mul(x_252_2, x)             # 2^252 - 3


def invert(x):
    """x^(p-2) = x^(2^255 - 21) = (x^(2^252-3))^8 * x^3."""
    t = sqn(pow2523(x), 3)
    return mul(t, mul(square(x), x))


# --- canonicalization / predicates -------------------------------------

_TWO_P = tuple(int(v) for v in raw_limbs(2 * P))
_P_LIMBS = tuple(int(v) for v in raw_limbs(P))


def canonical(x):
    """Return (limbs, ge_p): limbs canonical-nonneg with value in [0, 2p),
    plus a bool mask of lanes whose value is >= p.

    The fully-reduced value is ``limbs - ge_p * p``; parity of the canonical
    value is ``(limbs[0] & 1) ^ ge_p`` (p is odd).
    """
    x = carry(x, 4)              # limbs in (-2^13, 2^13 + WRAP]
    x = tuple(v + t for v, t in zip(x, _TWO_P))
    x = carry(x, 6)              # nonneg carries converge: limbs in [0, 2^13)
    # fold bits 255+ : limb 19 holds bits 247..259
    top = lax.shift_right_arithmetic(x[19], 8)
    x = (
        (x[0] + top * 19,)
        + x[1:19]
        + (jnp.bitwise_and(x[19], 255),)
    )
    x = carry(x, 2)
    # now value < 2^255 + ~600 < 2p, limbs canonical nonneg
    ge = jnp.zeros(_bshape(x), bool)
    eq_above = jnp.ones(_bshape(x), bool)
    for i in reversed(range(NLIMBS)):
        gt = x[i] > _P_LIMBS[i]
        lt = x[i] < _P_LIMBS[i]
        ge = ge | (eq_above & gt)
        eq_above = eq_above & ~gt & ~lt
    ge = ge | eq_above  # x == p counts as >= p
    return x, ge


def is_zero(x):
    """Exact test: value(x) ≡ 0 mod p (vectorized bool, shape = batch)."""
    limbs, _ = canonical(x)
    all_zero = jnp.ones(_bshape(limbs), bool)
    eq_p = jnp.ones(_bshape(limbs), bool)
    for i in range(NLIMBS):
        all_zero = all_zero & (limbs[i] == 0)
        eq_p = eq_p & (limbs[i] == _P_LIMBS[i])
    return all_zero | eq_p


def eq(a, b):
    return is_zero(sub(a, b))


def parity(x):
    """Parity bit of the canonical (fully reduced) value."""
    limbs, ge = canonical(x)
    return jnp.bitwise_xor(
        jnp.bitwise_and(limbs[0], 1), ge.astype(jnp.int32)
    )


# --- byte conversion (device) ------------------------------------------


def from_bytes_255(b):
    """bytes (32, N...) uint8 LE -> (limbs tuple, signbit (N...)).

    Bit 255 split off as the sign. ZIP-215 semantics: y values >= p are
    accepted; the redundant limb form carries the excess, later ops
    reduce mod p.
    """
    b = b.astype(jnp.int32)
    sign = lax.shift_right_arithmetic(b[31], 7)
    rows = [b[i] for i in range(32)]
    rows[31] = jnp.bitwise_and(rows[31], 0x7F)
    return _pack_limbs(rows, NLIMBS), sign


def from_bytes_256(b):
    """bytes (32, N...) uint8 LE -> 20 limbs of the full 256-bit integer."""
    b = b.astype(jnp.int32)
    return _pack_limbs([b[i] for i in range(32)], NLIMBS)


def _pack_limbs(rows, nlimbs: int):
    """rows: list of (N...) int32 byte vectors -> tuple of 13-bit limbs."""
    z = jnp.zeros_like(rows[0])
    rows = rows + [z, z]
    limbs = []
    for i in range(nlimbs):
        bit = LIMB_BITS * i
        byte, off = bit // 8, bit % 8
        v = (
            lax.shift_right_arithmetic(rows[byte], off)
            | (rows[byte + 1] << (8 - off))
            | (rows[byte + 2] << (16 - off))
        )
        limbs.append(jnp.bitwise_and(v, MASK))
    return tuple(limbs)


def select(mask, a, b):
    """Lane select: mask (N...,) bool -> where(mask, a, b) per limb."""
    return tuple(jnp.where(mask, x, y) for x, y in zip(a, b))
