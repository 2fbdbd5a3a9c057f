"""Batched ed25519 signature verification on TPU (the north-star kernel).

Replaces the reference's batch-verification seam — curve25519-voi's
``BatchVerifier`` created by ``crypto/batch/batch.go:10`` and consumed by
``types/validation.go:261 verifyCommitBatch`` — with one XLA program that
verifies N signatures in parallel lanes:

    per lane:  h  = SHA-512(R || A || M)  mod L          (on device)
               ok = [8]([S]B - [h]A - R) == identity     (ZIP-215, cofactored)

The double-scalar multiplication [S]B + [L-h]A runs as a shared 4-bit
windowed Straus ladder (64 windows x 4 doublings, one cached add from a
per-lane [d]A table and one affine-cached add from a host-precomputed
[d]B table per window, branch-free 16-way point selects), vectorized
over the batch on the 8x128 VPU lanes. All point/field math is int32
limb arithmetic (see fe25519).

Unlike the reference's random-linear-combination batch verify (which
rejects the whole batch on one bad signature and needs a CPU fallback
pass), every lane here returns its own verdict — a failed commit
verification can point at the exact bad vote with no re-verification.

The cofactored equation with per-lane verdicts is exactly ZIP-215, so
results match curve25519-voi vote-by-vote (reference
types/validation.go:261-320 semantics, including its all-or-nothing
fallback behavior, can be reproduced by AND-reducing the lane mask).
"""

from __future__ import annotations

import functools
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..crypto.lanes import LaneBatch
from ..trace import current_ticket, global_tracer
from ..utils import device
from . import curve25519 as curve
from . import fe25519 as fe
from . import sc25519 as sc
from . import sha512

# message capacity buckets: hash input is 64 + cap bytes; choosing
# cap = 128k - 64 - 17 makes the padded hash input exactly k blocks.
MSG_CAPS = (47, 175, 431, 943)


def bucket_cap(max_len: int) -> int:
    for c in MSG_CAPS:
        if max_len <= c:
            return c
    raise ValueError(f"message too long for verify kernel: {max_len}")


_B_TABLE = None


def _b_table():
    global _B_TABLE
    if _B_TABLE is None:
        _B_TABLE = curve.base_window_table()  # (16, 3, 20) host const
    return _B_TABLE


def _straus(ds, dh, A, shape):
    """[s]B + [hneg]A over batch lanes (tuple-of-limbs field elements).

    4-bit windowed joint ladder: 64 windows x (4 doublings) — the first
    group acts on the identity — plus per window one add from the
    per-lane A table and one affine-cached add from the shared
    host-precomputed B table (7M). ~27% fewer field multiplies than the
    bitwise ladder (253 x (double + 9M add)), and the window tables'
    d=0 entries are the identity in cached form so the adds stay
    branch-free and complete.

    The A table is built ON DEVICE from the extended point ``A`` (15
    sequential curve.add's, cached-projective entries). A round-3
    experiment replaced it with host-precomputed (16, 3, 20, N) tables
    to shrink the HLO for the XLA CPU backend: the gather lookup form
    ran ~4x slower on TPU (breaks tuple-of-limbs fusion), the
    select-forest lookup form compiled for >26 min on the TPU backend,
    and NEITHER made the CPU-backend compile finish (>60 min on the
    1-core box in every variant) — so the on-device build stays
    (docs/PERF.md "CPU-backend compile pathology").

    ds / dh: (64, N) int32 window digits, LSB-first."""
    # backend precedence: GRAFT_PALLAS=1/0 forces pallas/XLA; unset =
    # pallas by default on accelerator backends at bulk widths only
    # (>= pallas_ladder.min_lanes(), whose docstring holds the chip's
    # readings — the interpreter stands in off-TPU), else compact on
    # the CPU backend, else the tuple-form XLA ladder. Every branch
    # condition here is part of _ladder_backend_key so a mid-process
    # flip retraces (the width itself re-keys via the per-shape jit
    # trace). pallas_ladder.ladder_for is the one test: the dispatch's
    # ``ladder`` tag (_pack) reports what it decides here, so a width
    # with no VMEM-safe blocking is tagged, and run, as XLA.
    if len(shape) == 1:
        from .pallas_ladder import ladder_for, straus_pallas

        if ladder_for(shape[0]) == "pallas":
            return straus_pallas(ds, dh, A, shape)
    if fe.compact_mode():
        return _straus_compact(ds, dh, A, shape)
    ident = curve.identity(shape)

    # per-lane A table: cached([d]A) for d in 0..15 — kept as a list of
    # 16 tuple-form points; selection is per-limb select_n over (N,)
    # vectors (no stacked gather, no broadcasts)
    ext = ident
    a_cached = [curve.to_cached(ident)]
    for _ in range(15):
        ext = curve.add(ext, A)
        a_cached.append(curve.to_cached(ext))

    # shared B table: (16, 3, 20) host constants; selected per limb as
    # scalar-broadcast cases (constant-folded by XLA)
    bt = _b_table()  # numpy (16, 3, 20) int32

    def body(i, q):
        j = 63 - i
        d_s = lax.dynamic_index_in_dim(ds, j, 0, keepdims=False)
        d_h = lax.dynamic_index_in_dim(dh, j, 0, keepdims=False)
        # only the last double's T is consumed (by the A window add);
        # the window-final add's T is never read (next op is a double)
        q = curve.double(
            curve.double(
                curve.double(curve.double(q, need_t=False), need_t=False),
                need_t=False,
            )
        )
        addend_a = tuple(
            tuple(
                lax.select_n(
                    d_h, *[a_cached[d][k][lj] for d in range(16)]
                )
                for lj in range(fe.NLIMBS)
            )
            for k in range(4)
        )
        q = curve.add_cached(q, addend_a)
        # shared B term: scalar-broadcast cases constant-folded by XLA
        addend_b = tuple(
            tuple(
                lax.select_n(
                    d_s,
                    *[
                        jnp.broadcast_to(
                            jnp.int32(int(bt[d, k, lj])), shape
                        )
                        for d in range(16)
                    ],
                )
                for lj in range(fe.NLIMBS)
            )
            for k in range(3)
        )
        return curve.add_affine_cached(q, addend_b, need_t=False)

    # T-less carry: the loop output feeds add_projective (no T input).
    return lax.fori_loop(0, 64, body, ident[:3] + (None,))


def _stack_pt(p):
    """tuple-form point -> stacked (ncomp, 20, N...) int32 array."""
    return jnp.stack([fe.stack(c) for c in p])


def _unstack_pt(arr):
    """stacked (ncomp, 20, N...) -> tuple-form point."""
    return tuple(
        tuple(arr[k, i] for i in range(fe.NLIMBS))
        for k in range(arr.shape[0])
    )


def _straus_compact(ds, dh, A, shape):
    """Compact-mode ladder for the XLA CPU backend: identical window
    schedule to _straus, but the per-lane A table is built by a
    15-step lax.scan into ONE stacked (16, 4, 20, N) array and window
    entries are fetched with take_along_axis instead of 16-way
    select_n trees. On TPU the gather form measured ~4x slower (it
    breaks tuple-of-limbs fusion — docs/PERF.md round-3 record), but
    here the target is compile-tractability: together with the rolled
    field ops it takes the CPU backend's compile from >80 min to
    seconds, which is what lets the virtual-mesh dryrun and the CPU
    test lane execute the REAL kernel graph (VERDICT r3 #1/#4)."""
    ident = curve.identity(shape)

    def build_step(ext_st, _):
        ext = _unstack_pt(ext_st)
        nxt = curve.add(ext, A)
        return _stack_pt(nxt), _stack_pt(curve.to_cached(nxt))

    _, entries = lax.scan(
        build_step, _stack_pt(ident), None, length=15
    )
    table = jnp.concatenate(
        [_stack_pt(curve.to_cached(ident))[None], entries], axis=0
    )  # (16, 4, 20, N)

    bt = jnp.asarray(_b_table())  # (16, 3, 20) int32 host consts

    def body(i, q):
        j = 63 - i
        d_s = lax.dynamic_index_in_dim(ds, j, 0, keepdims=False)
        d_h = lax.dynamic_index_in_dim(dh, j, 0, keepdims=False)
        q = curve.double(
            curve.double(
                curve.double(curve.double(q, need_t=False), need_t=False),
                need_t=False,
            )
        )
        idx = jnp.broadcast_to(
            d_h[None, None, None], (1,) + table.shape[1:]
        )
        ac = jnp.take_along_axis(table, idx, axis=0)[0]  # (4, 20, N)
        q = curve.add_cached(q, _unstack_pt(ac))
        ab = jnp.take(bt, d_s, axis=0)  # (N, 3, 20)
        addend_b = tuple(
            tuple(ab[..., k, lj] for lj in range(fe.NLIMBS))
            for k in range(3)
        )
        return curve.add_affine_cached(q, addend_b, need_t=False)

    return lax.fori_loop(0, 64, body, ident[:3] + (None,))


def _verify_core(msgs, lens, pks, rs, ss):
    """msgs (cap, N) uint8; lens (N,) int32; pks/rs/ss (32, N) uint8.

    Returns bool (N,): per-signature ZIP-215 verdicts.
    """
    cap = msgs.shape[0]
    n = pks.shape[1]
    # one decompression over [pks | rs]: the square-root exponentiation
    # is a ~254-deep sequential squaring chain whose cost is dominated
    # by depth, not lane count — sharing it across both points halves
    # that depth instead of paying it twice
    both, ok_both = curve.decompress(
        jnp.concatenate([pks, rs], axis=1)
    )
    A = tuple(tuple(c[:n] for c in comp) for comp in both)
    R = tuple(tuple(c[n:] for c in comp) for comp in both)
    ok_a, ok_r = ok_both[:n], ok_both[n:]
    s = fe.from_bytes_256(ss)
    ok_s = sc.lt_L(s)

    hin = jnp.concatenate([rs, pks, msgs], axis=0)
    digest = sha512.sha512(hin, lens + 64, cap + 64)
    h = sc.reduce_512(sc.hash_bytes_to_limbs(digest))
    hneg = sc.neg_mod_L(h)

    q = _straus(sc.digits4(s), sc.digits4(hneg), A, (n,))
    p8 = curve.mul_by_cofactor(
        curve.add_projective(q, (fe.neg(R[0]), R[1], R[2]))
    )
    return ok_a & ok_r & ok_s & curve.is_identity(p8)


def _verify_core_precomp(msgs, lens, a_arr, pks, rs, ss):
    """Verify with HOST-decompressed public keys (the expanded-pubkey
    LRU, reference crypto/ed25519/ed25519.go:31, moved on-device).

    a_arr (4, 20, N) int32: A in affine-extended limb form (x, y, 1,
    x*y), produced once per distinct key by the host cache. Validator
    sets repeat across blocks — a 10k-block replay has ~150 distinct
    keys for ~1.5M lanes — so only R still pays the ~254-deep sqrt
    chain, halving the decompression stage's depth-dominated cost.
    pks is still an input: the hash is SHA-512(R || A_bytes || M).

    Delegates to the tuple-form body after unpacking the stacked A —
    ONE verification body serves both dispatch modes (the modes must
    stay bit-identical; tests assert it).
    """
    A = tuple(
        tuple(a_arr[k, j] for j in range(fe.NLIMBS)) for k in range(4)
    )
    return _verify_core_precomp_tuple(msgs, lens, A, pks, rs, ss)


def _ladder_backend_key() -> tuple:
    """Everything the traced verify program branches on at TRACE time:
    ladder backend (pallas opt-in), field mode (compact vs tuple), and
    the pallas sublane blocking. The jit wrappers below are cached PER
    KEY, so flipping GRAFT_PALLAS / GRAFT_COMPACT_FIELD /
    GRAFT_PALLAS_SUBLANES mid-process retraces instead of silently
    reusing a stale trace (VERDICT r4 weak #6)."""
    from .pallas_ladder import block_sublanes, min_lanes, pallas_enabled

    # pallas_enabled(None) here = "may pallas engage at SOME width";
    # the actual per-width choice lives in _straus and re-keys via the
    # per-shape jit trace, so min_lanes() must key the wrapper too
    pallas = pallas_enabled()
    return (
        "pallas" if pallas else "xla",
        fe.compact_mode(),
        block_sublanes() if pallas else 0,
        min_lanes() if pallas else 0,
    )


def _verify_core_precomp_tuple(msgs, lens, a_tree, pks, rs, ss):
    """Precomp verify with A handed over as a PYTREE of 80 separate
    (N,) int32 arrays instead of one stacked (4, 20, N) input
    (docs/PERF.md lever #6, round-5). The stacked form loses at bulk
    widths (550 vs 363 ms @131072) because slicing it back apart
    defeats tuple-of-limbs fusion; jit boundaries accept pytrees, so
    this variant preserves the tuple form end to end while still
    skipping A's half of the depth-bound sqrt chain. Opt-in via
    GRAFT_PRECOMP_TUPLE=1 pending a silicon A/B (not shipped blind).
    """
    cap = msgs.shape[0]
    n = rs.shape[1]
    A = a_tree
    R, ok_r = curve.decompress(rs)
    s = fe.from_bytes_256(ss)
    ok_s = sc.lt_L(s)

    hin = jnp.concatenate([rs, pks, msgs], axis=0)
    digest = sha512.sha512(hin, lens + 64, cap + 64)
    h = sc.reduce_512(sc.hash_bytes_to_limbs(digest))
    hneg = sc.neg_mod_L(h)

    q = _straus(sc.digits4(s), sc.digits4(hneg), A, (n,))
    p8 = curve.mul_by_cofactor(
        curve.add_projective(q, (fe.neg(R[0]), R[1], R[2]))
    )
    return ok_r & ok_s & curve.is_identity(p8)


def precomp_tuple_enabled() -> bool:
    return os.environ.get("GRAFT_PRECOMP_TUPLE") == "1"


def a_tree_from_stacked(a_arr, put=jnp.asarray):
    """Host-side: stacked (4, NLIMBS, N) numpy A -> the pytree of 80
    separate (N,) arrays the tuple kernel takes, each through ``put``
    (onto the device, or left on the host for a sharded program)."""
    return tuple(
        tuple(
            put(np.ascontiguousarray(a_arr[k, j]))
            for j in range(fe.NLIMBS)
        )
        for k in range(4)
    )


@functools.lru_cache(maxsize=None)
def _keyed_jit(kind: str, key: tuple):
    core = {
        "plain": _verify_core,
        "precomp": _verify_core_precomp,
        "precomp_tuple": _verify_core_precomp_tuple,
    }[kind]
    return jax.jit(core)


def verify_core_jit(msgs, lens, pks, rs, ss):
    return _keyed_jit("plain", _ladder_backend_key())(
        msgs, lens, pks, rs, ss
    )


def verify_core_precomp_jit(msgs, lens, a_arr, pks, rs, ss):
    return _keyed_jit("precomp", _ladder_backend_key())(
        msgs, lens, a_arr, pks, rs, ss
    )


def verify_core_precomp_tuple_jit(msgs, lens, a_tree, pks, rs, ss):
    return _keyed_jit("precomp_tuple", _ladder_backend_key())(
        msgs, lens, a_tree, pks, rs, ss
    )


# --- host-side expanded-pubkey cache -----------------------------------
# pk bytes -> (4, 20) int32 affine-extended limbs, or None for keys
# that fail ZIP-215 decompression. LRU, like the reference's expanded
# ed25519 key cache (crypto/ed25519/ed25519.go:31).
_A_CACHE: "dict" = {}
_A_CACHE_MAX = 4096


def _expand_pubkey(pk: bytes):
    if pk in _A_CACHE:
        return _A_CACHE[pk]
    from ..crypto import ref_ed25519 as _ref

    pt = _ref.point_decompress(pk)
    if pt is None:
        val = None
    else:
        x, y, _z, t = pt
        val = np.stack(
            [
                fe.raw_limbs(x),
                fe.raw_limbs(y),
                fe.raw_limbs(1),
                fe.raw_limbs(t),
            ]
        )  # (4, 20) int32
    if len(_A_CACHE) >= _A_CACHE_MAX:
        _A_CACHE.pop(next(iter(_A_CACHE)))
    _A_CACHE[pk] = val
    return val


# minimum lane padding; shrunk by the multichip dryrun so its one
# kernel compile happens at tiny per-device shapes
PAD_MIN = 128

# Width cutoff between the two kernels (measured on v5e, uncontended):
# - small batches: the ~254-deep decompression chain dominates, so the
#   precomp kernel (host-expanded A, only R pays the sqrt chain) wins;
# - large batches: depth amortizes across lanes and the precomp path's
#   stacked (4,20,N) A input costs MORE than it saves (slice reads
#   defeat the tuple-of-limbs fusion: 550ms vs 363ms @131072 lanes).
PRECOMP_MAX_LANES = 4096

# Lanes transposed at a time when _pack turns (lane, byte) rows into
# the kernel's batch-last layout: the width of the one-chip cells'
# dispatch, which is therefore ONE block; a wider batch (the mesh's
# 65,536 lanes) goes in few large blocks, one array operation each.
# Wider, and the rows fall out of the cache between two reads of a
# column; narrower, and the operations' GIL hand-backs outweigh it.
PACK_BLOCK = 16_384


def _pad_n(n: int) -> int:
    """Pad batch to limit recompilation: powers of two >= PAD_MIN."""
    p = PAD_MIN
    while p < n:
        p *= 2
    return p


# Mesh-aware dispatch: when more than one local device is visible the
# batch is lane-sharded over all of them (data parallelism over
# signature lanes — the framework's ICI scaling axis, SURVEY.md §2.2).
# Keyed by device count; jitted shard_map programs are cached here.
_SHARDED_FNS: dict = {}

# Introspection for tests/dryrun: how the last verify_batch dispatched.
LAST_DISPATCH: dict = {}


def _sharded_fn(mode: str):
    """(fn, shardings of its arguments): the lane-sharded verify over
    all local devices, or (None, None) on a single device. ``mode``:
    "plain" | "precomp" | "precomp_tuple"."""
    n = device.backend().count
    if n <= 1:
        return None, None
    # backend key: the sharded program traces through _straus too, so
    # a mid-process backend flip must map to a fresh shard_map program
    key = (n, mode, _ladder_backend_key())
    if key not in _SHARDED_FNS:
        from ..parallel.mesh import make_mesh
        from ..parallel.sharded_verify import (
            core_shardings,
            make_sharded_core,
        )

        mesh = make_mesh(n)
        _SHARDED_FNS[key] = (
            make_sharded_core(mesh, mode), core_shardings(mesh, mode)
        )
    return _SHARDED_FNS[key]


class AsyncVerdicts:
    """Handle for an in-flight verify dispatch (XLA dispatch is async:
    the program is enqueued and this handle holds the device future).
    ``result()`` blocks and returns the bool verdicts. Overlapping
    several dispatches before resolving amortizes the per-dispatch
    flat cost — the production pipelining seam."""

    def __init__(self, res, bad, n, devices=1):
        self._res = res
        self._bad = bad
        self._n = n
        self._devices = devices

    def wait(self) -> "AsyncVerdicts":
        """Block until the device computation is READY, without
        fetching the verdicts to host (thread-safe; used by the
        routing calibration's readiness watcher in crypto/scheduler). ``block_until_ready`` blocks on an attached
        chip: chip_smoke.py times it against the fetch that follows."""
        bur = getattr(self._res, "block_until_ready", None)
        if bur is not None:
            bur()
        return self

    def result(self) -> np.ndarray:
        """The verdicts on the host. The device-to-host read (from
        every device that holds a shard of them) is the span
        ``ops.ed25519.fetch``, for the ticket the calling thread works
        for, as ``verify_batch_async``'s spans are."""
        ticket, tid = current_ticket()
        with global_tracer().annotated_span(
            "ops.ed25519.fetch", tid=tid or "ops.ed25519", ticket=ticket,
            devices=self._devices, lanes=len(self._bad),
        ):
            out = np.array(self._res)[: self._n]
        out[self._bad[: self._n]] = False
        return out


def verify_batch_async(items) -> AsyncVerdicts:
    """Enqueue one verify dispatch WITHOUT blocking on the verdicts
    (see AsyncVerdicts). Same prep/dispatch as verify_batch.

    ``items``: a crypto/lanes.LaneBatch (the lanes by columns, as the
    verify seam hands them through the scheduler) or a sequence of
    ``(msg, key_bytes, sig)`` tuples, turned into one HERE, inside the
    ``pack`` span: below this boundary a batch has one form.

    Two stage spans on the process tracer (docs/TRACE.md "One ticket,
    one timeline"), carrying the verify ticket the calling thread
    works for (trace.ticket_scope; None when called directly):
    ``ops.ed25519.pack`` (the bulk fill of the padded arrays; its
    ``form`` says how the lanes arrived, ``columns`` | ``tuples``, its
    ``blocks`` in how many lane blocks they were transposed, its
    ``bad`` counts the lanes refused before the device; in the precomp
    forms ``keys`` counts the dispatch's distinct keys and ``expanded``
    those ``_expand_pubkey`` did not find in ``_A_CACHE``) and
    ``ops.ed25519.enqueue`` (placement of the host arrays, its child
    span ``ops.ed25519.put``, and the jitted call). Both say the
    ``ladder`` the program runs the dispatch's lanes on, ``pallas`` or
    ``xla`` (pallas_ladder.ladder_for)."""
    n = len(items)
    if n == 0:
        return AsyncVerdicts(np.zeros(0, bool), np.zeros(0, bool), 0)
    tr = global_tracer()
    ticket, tid = current_ticket()
    tid = tid or "ops.ed25519"
    columnar = isinstance(items, LaneBatch)
    with tr.annotated_span(
        "ops.ed25519.pack", tid=tid, ticket=ticket, sigs=n,
        form="columns" if columnar else "tuples",
    ) as sp:
        fn, arrays, tuple_a, shardings, bad = _pack(
            items if columnar else LaneBatch.from_items(items)
        )
        d = LAST_DISPATCH
        sp.set(
            lanes=d["lanes"], cap=d["cap"], mode=d["mode"],
            ladder=d["ladder"], bad=int(np.count_nonzero(bad)),
            devices=d["n_devices"],
            lanes_per_device=d["lanes"] // d["n_devices"],
            blocks=-(-n // PACK_BLOCK),
        )
        if d["precomp"]:
            # the host-expanded-key LRU's work for this dispatch
            sp.set(keys=d["keys"], expanded=d["expanded"])
    nbytes = sum(a.nbytes for a in arrays)
    with tr.annotated_span(
        "ops.ed25519.enqueue", tid=tid, ticket=ticket,
        lanes=d["lanes"], ladder=d["ladder"], bytes=nbytes,
    ):
        with tr.annotated_span(
            "ops.ed25519.put", tid=tid, ticket=ticket,
            devices=d["n_devices"], bytes=nbytes,
        ):
            args = _put(arrays, tuple_a, shardings)
        # XLA dispatch is async: the call returns the device future
        res = fn(*args)
    return AsyncVerdicts(res, bad, n, d["n_devices"])


# one 32-byte key as one array element: distinct keys by np.unique
_KEY32 = np.dtype((np.void, 32))


def _batch_last(rows, refused, lanes: int):
    """(n, width) rows, one a lane -> (width, lanes) C-contiguous,
    the ``refused`` lanes and those past n zero; transposed a block of
    PACK_BLOCK lanes at a time (a wider block's rows fall out of the
    cache between two reads of a column)."""
    n = len(rows)
    out = np.zeros((rows.shape[1], lanes), np.uint8)
    for lo in range(0, n, PACK_BLOCK):
        hi = min(lo + PACK_BLOCK, n)
        out[:, lo:hi] = rows[lo:hi].T
    if len(refused):
        out[:, refused] = 0
    return out


def _pack(batch: LaneBatch):
    """Bucket and kernel choice, and the bulk fill of the padded host
    arrays from a crypto/lanes.LaneBatch, the lanes by columns (the
    tuple form is turned into one at verify_batch_async): the
    messages are measured, padded to the bucket and joined (three
    C-level passes: they are ragged), keys and signatures come as
    rows, and every field goes to the kernel's batch-last layout
    PACK_BLOCK lanes at a time, with no Python step a lane and no
    transpose wider than a block. Returns (fn, host
    arrays in argument order, whether A goes as a pytree, the sharded
    program's argument shardings or None on one device, bad lanes);
    LAST_DISPATCH says the shape.

    A lane is ``bad`` (refused before the device, all zero in every
    array) when its key is not 32 bytes, its signature not 64 (the
    batch's ``bad``), or, in the precomp forms, its key fails ZIP-215
    decompression."""
    n = len(batch)
    ms = batch.msgs
    m_lens = np.fromiter(map(len, ms), np.int32, n)
    cap = bucket_cap(int(m_lens.max()))  # over ALL items, bad ones too
    np_ = _pad_n(n)
    n_dev = device.backend().count
    if np_ % n_dev:
        np_ += n_dev - (np_ % n_dev)

    # kernel choice by PER-DEVICE lane width (see PRECOMP_MAX_LANES):
    # precomp (host-expanded A) below the cutoff — the depth-bound
    # decompression dominates there — plain above it, where depth
    # amortizes and the stacked A input costs more than it saves
    # (unless the tuple-form A opt-in is on, docs/PERF.md lever #6)
    use_precomp = (np_ // n_dev) <= PRECOMP_MAX_LANES
    tuple_a = use_precomp and precomp_tuple_enabled()
    mode = (
        "precomp_tuple"
        if tuple_a
        else ("precomp" if use_precomp else "plain")
    )
    sharded, shardings = _sharded_fn(mode)

    refused = batch.bad
    a_arr = None
    if use_precomp:
        # each DISTINCT key is expanded once a dispatch (a validator
        # set has a few hundred of them for thousands of lanes)
        good = np.ones(n, bool)
        good[refused] = False
        uniq, inverse = np.unique(
            batch.keys[good].view(_KEY32).ravel(), return_inverse=True
        )
        table = np.zeros((4, fe.NLIMBS, len(uniq)), np.int32)
        key_ok = np.zeros(len(uniq), bool)
        expanded = 0  # keys the LRU did not hold
        for j, key in enumerate(uniq):
            key = key.tobytes()
            expanded += key not in _A_CACHE
            A = _expand_pubkey(key)
            if A is not None:  # else: fails ZIP-215 decompression
                table[:, :, j] = A
                key_ok[j] = True
        a_arr = np.zeros((4, fe.NLIMBS, np_), np.int32)
        a_arr[:, :, np.flatnonzero(good)] = table[:, :, inverse]
        good[good] = key_ok[inverse]
        refused = np.flatnonzero(~good)
    bad = np.zeros(np_, bool)
    bad[refused] = True

    # the messages padded to cap and joined (one C-level pass that
    # keeps the GIL) are their (lane, byte) rows; every field then
    # goes to the kernel's batch-last layout in few LARGE array
    # operations: each gives the GIL away and waits for it while
    # another thread runs Python, so a dozen small ones cost more
    # than the copying they do (PERF.md, PR 36)
    lens = np.zeros(np_, np.int32)
    lens[:n] = m_lens
    lens[refused] = 0
    m_rows = np.frombuffer(
        b"".join(
            map(bytes.ljust, ms, itertools.repeat(cap), itertools.repeat(b"\0"))
        ),
        np.uint8,
    ).reshape(n, cap)
    sigs = batch.sigs
    msgs, pks, rs, ss = (
        _batch_last(rows, refused, np_)
        for rows in (m_rows, batch.keys, sigs[:, :32], sigs[:, 32:])
    )

    # the ladder the kernel runs at this dispatch's per-device width
    # (pallas_ladder.ladder_for, the test _straus applies), not merely
    # whether pallas may engage
    from .pallas_ladder import interpret_mode, ladder_for

    ladder = ladder_for(np_ // n_dev)
    LAST_DISPATCH.clear()
    LAST_DISPATCH.update(
        sharded=sharded is not None,
        n_devices=n_dev,
        lanes=np_,
        cap=cap,
        precomp=use_precomp,
        mode=mode,
        ladder=ladder,
        backend_key=(ladder,) + _ladder_backend_key()[1:],
        # the Pallas INTERPRETER ran the ladder (CPU platform only)
        interpret=ladder == "pallas" and interpret_mode(),
    )
    if use_precomp:
        LAST_DISPATCH.update(keys=len(uniq), expanded=expanded)
    if tuple_a:
        fn = sharded or verify_core_precomp_tuple_jit
    elif use_precomp:
        fn = sharded or verify_core_precomp_jit
    else:
        fn = sharded or verify_core_jit
    arrays = (
        (msgs, lens, a_arr, pks, rs, ss)
        if use_precomp
        else (msgs, lens, pks, rs, ss)
    )
    return fn, arrays, tuple_a, shardings, bad


def _put(arrays, tuple_a: bool, shardings):
    """The host arrays placed for the program, in argument order. On
    one device each is a ``jnp.asarray``; the sharded program's go by
    its own argument shardings (parallel/sharded_verify
    .core_shardings), each device its own lanes, where ``jnp.asarray``
    would first land every array whole on device 0."""
    if tuple_a:
        # pytree A: 80 separate (N,) arrays, preserving tuple-of-limbs
        # fusion across the jit boundary (lever #6)
        msgs, lens, a_arr, pks, rs, ss = arrays
        arrays = (
            msgs, lens, a_tree_from_stacked(a_arr, lambda a: a),
            pks, rs, ss,
        )
    if shardings is None:
        return jax.tree.map(jnp.asarray, arrays)
    return jax.device_put(arrays, shardings)


def verify_batch(items) -> np.ndarray:
    """Host API: items = list of (msg: bytes, pubkey: 32B, sig: 64B).

    Returns np.ndarray of bool verdicts, one per item. Builds padded
    device arrays (batch-last layout), dispatches one XLA program —
    lane-sharded over every local device when a multi-chip mesh is
    available (same shard_map program the driver dryrun validates).

    Public keys are decompressed ONCE per distinct key on the host
    (LRU) and fed to the kernel in limb form: validator sets repeat
    across commits, so the device-side sqrt chain only runs for the R
    points (the reference's expanded-key LRU, ed25519.go:31).
    """
    return verify_batch_async(items).result()
