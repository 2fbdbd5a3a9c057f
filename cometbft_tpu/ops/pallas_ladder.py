"""Pallas (Mosaic) Straus ladder: VMEM-resident window tables.

The double-scalar ladder is ~60% of the verify kernel's runtime
(docs/PERF.md ablations). Under plain XLA the per-lane 16-entry window
table (5.1 KB/lane) streams through HBM on every one of the 64 windows
— ~43 GB of table traffic per 131072-lane dispatch — because each
field element is ~10.5 MB at bulk widths and nothing fits in VMEM
across windows. This kernel blocks the lanes so that, per grid step,
the table slice, the digit planes and the accumulator point all live
in VMEM for the whole 64-window loop: table bytes move from HBM once
per dispatch instead of 64 times, and Mosaic schedules the double/add
chains directly.

The field math inside the kernel body is the SAME tuple-of-limbs code
as the XLA path (ops/fe25519, ops/curve25519) — limbs are (S, 128)
int32 tiles sliced from VMEM refs, and every op is elementwise on
them, which is exactly what the VPU wants. The window schedule is
identical to ops/ed25519._straus, so verdicts are bit-identical.

Replaces the hot loop behind the reference's batch-verification seam
(curve25519-voi Straus ladder used by crypto/ed25519 verification);
an original design for the TPU memory hierarchy, not a port.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device
from . import curve25519 as curve
from . import fe25519 as fe

# lanes per grid step = block_sublanes() * 128. Mosaic requires the
# sublane (second-to-minor) block dim to be a multiple of 8 — or the
# whole array dim — so 8 sublanes (1024 lanes) is the FLOOR at bulk
# widths, not a tuning choice; the r5 first-contact sweep's 4-sublane
# leg failed lowering on exactly that check
# (jax/_src/pallas/mosaic/lowering.py:_check_block_mappings). At 8
# sublanes the table slice is 5.2 MB; with Pallas's default
# double-buffering plus digit planes the working set fits the ~16 MB
# VMEM budget (compiles and runs on v5e silicon, r5). Bench-tunable
# via GRAFT_PALLAS_SUBLANES; tests may pin the module attribute.
BLOCK_SUBLANES = None  # None = read GRAFT_PALLAS_SUBLANES (default 8)


def block_sublanes() -> int:
    if BLOCK_SUBLANES is not None:
        return BLOCK_SUBLANES
    return int(os.environ.get("GRAFT_PALLAS_SUBLANES", "8"))


def min_lanes() -> int:
    """Width floor for the default-on pallas ladder (bulk widths
    only). The chip's readings (TPU v5e device trace, PERF.md): at
    16,384 lanes this ladder's verify program took 17.08 ms against
    the XLA ladder's 50.13 on one chip, 16.97 against 67.76 a device
    on four, but took longer to trace and lower (156.5 against 105.8 s
    of set-up) and left the host to set the pace, so the cells built
    at 16,384 lanes keep the XLA ladder; the floor stays where
    ``val150.verify-only.512`` runs this ladder, 65,536 lanes on one
    chip: 64.68 ms a dispatch, 37.80 of them the ladder. Compile cost
    is no reason either way: ~13 min a lane bucket cold with either
    ladder (757 s XLA at 32,768 lanes, 785 s Pallas at 65,536); the
    compilation cache hits for both, the Pallas program only from the
    same checkout path with the kernel's sources unmoved (Mosaic's
    payload keeps their MLIR locations)."""
    return int(os.environ.get("GRAFT_PALLAS_MIN_LANES", "65536"))


def pallas_enabled(n: "int | None" = None) -> bool:
    """Ladder backend selection: GRAFT_PALLAS=1 forces pallas at
    every width (tests, A/B legs), GRAFT_PALLAS=0 forces the XLA
    ladder; otherwise pallas is the DEFAULT on accelerator backends at
    bulk widths (n >= min_lanes()) and off elsewhere. Read dynamically
    AND safely flippable mid-process: the verify jit wrappers are
    keyed by (ladder backend, field mode, sublanes, min-lanes) —
    ops/ed25519._ladder_backend_key — so an env flip reaches the next
    verify_batch instead of silently hitting a stale cached trace
    (VERDICT r4 weak #6)."""
    v = os.environ.get("GRAFT_PALLAS")
    if v == "1":
        return True
    if v == "0":
        return False
    if n is not None and n < min_lanes():
        return False
    return not device.on_cpu()


def interpret_mode() -> bool:
    """The Pallas interpreter stands in for Mosaic on the CPU platform
    only (tests, the virtual-device dryrun); never on an accelerator."""
    return device.on_cpu()


def _tree_select16(digit, entries):
    """16-way table lookup as a 4-level binary select tree.

    Mosaic's select_n lowering only supports 2 cases
    (jax/_src/pallas/mosaic/lowering.py:_select_n_lowering_rule — the
    bench's first silicon contact failed exactly there), so the
    window-digit lookup selects on one digit bit per level: entry
    index d = b0 + 2*b1 + 4*b2 + 8*b3. Same function as
    lax.select_n(digit, *entries); 15 two-way selects per limb."""
    lvl = list(entries)
    for k in range(4):
        bit = lax.shift_right_logical(digit, k) & 1
        pred = bit != 0
        lvl = [
            lax.select_n(pred, lvl[2 * i], lvl[2 * i + 1])
            for i in range(len(lvl) // 2)
        ]
    return lvl[0]


def _ladder_kernel(ds_ref, dh_ref, table_ref, out_ref):
    """One lane block: table_ref (16, 4, 20, S, 128) VMEM; ds/dh
    (64, S, 128); out_ref (3, 20, S, 128) = X, Y, Z of the ladder
    result (T-less carry, same as _straus)."""
    s = table_ref.shape[3]
    shape = (s, 128)
    ident = curve.identity(shape)

    # B window table: shared host constants, broadcast per lane
    from .ed25519 import _b_table

    bt = _b_table()  # numpy (16, 3, 20)

    def body(i, q):
        j = 63 - i
        d_s = ds_ref[j]
        d_h = dh_ref[j]
        q = curve.double(
            curve.double(
                curve.double(curve.double(q, need_t=False), need_t=False),
                need_t=False,
            )
        )
        addend_a = tuple(
            tuple(
                _tree_select16(
                    d_h, [table_ref[d, k, lj] for d in range(16)]
                )
                for lj in range(fe.NLIMBS)
            )
            for k in range(4)
        )
        q = curve.add_cached(q, addend_a)
        addend_b = tuple(
            tuple(
                _tree_select16(
                    d_s,
                    [
                        jnp.full(shape, int(bt[d, k, lj]), jnp.int32)
                        for d in range(16)
                    ],
                )
                for lj in range(fe.NLIMBS)
            )
            for k in range(3)
        )
        return curve.add_affine_cached(q, addend_b, need_t=False)

    q = lax.fori_loop(0, 64, body, ident[:3] + (None,))
    for k in range(3):
        for lj in range(fe.NLIMBS):
            out_ref[k, lj] = q[k][lj]


def effective_block(block: int, r: int) -> "int | None":
    """The sublane-block height the kernel will actually run for a
    configured ``block`` over ``r`` sublane rows, or None when no
    VMEM-safe Mosaic-valid blocking exists (caller falls back to the
    XLA ladder).

    Constraints (r5 silicon contact): the height must DIVIDE r (a
    remainder block would silently drop rows — uninitialized verdict
    lanes, code-review r4), and Mosaic requires it to be a multiple
    of 8 OR the whole dim. The fallback never grows past
    max(block, 8): the whole-dim escape at large odd r would build an
    unbounded VMEM block (r=513 -> a ~333 MB table slice) — an
    explicitly configured larger block is honored (the operator is
    sweeping), but the automatic fallback stays at proven sizes."""
    cap = max(block, 8)
    best = None
    for d in range(8, min(r, cap) + 1, 8):
        if r % d == 0:
            best = d
    if best is not None:
        return best
    if r <= cap:
        return r  # whole dim (== r) is Mosaic-valid and small
    return None


@functools.partial(
    jax.jit, static_argnames=("block", "interpret")
)
def _ladder_call(ds, dh, table, block=8, interpret=False):
    """ds/dh (64, R, 128) int32; table (16, 4, 20, R, 128) int32 ->
    (3, 20, R, 128) int32 (X, Y, Z tuple-of-limbs, carried).

    ``block`` is the EFFECTIVE sublane-block height (the caller runs
    effective_block() first) and is a STATIC arg: it shapes the grid,
    so it must key this function's own jit cache — a mid-process
    GRAFT_PALLAS_SUBLANES change then retraces instead of silently
    reusing the old blocking."""
    r = ds.shape[1]
    s = block
    assert r % s == 0 and (s % 8 == 0 or s == r), (s, r)
    grid = (r // s,)
    return pl.pallas_call(
        _ladder_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (64, s, 128), lambda i: (0, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (64, s, 128), lambda i: (0, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (16, 4, fe.NLIMBS, s, 128),
                lambda i: (0, 0, 0, i, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (3, fe.NLIMBS, s, 128),
            lambda i: (0, 0, i, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (3, fe.NLIMBS, r, 128), jnp.int32
        ),
        interpret=interpret,
    )(ds, dh, table)


def straus_pallas(ds, dh, A, shape, interpret=None):
    """Drop-in for ops/ed25519._straus on lane counts that are
    multiples of 128: [s]B + [hneg]A via the VMEM-blocked kernel.

    ds/dh: (64, N) digit planes; A: tuple-form extended point; returns
    the T-less (X, Y, Z, None) tuple-of-limbs point, matching _straus.
    The per-lane A window table is built in XLA (15 sequential cached
    adds, the same build as _straus) and handed to the kernel stacked —
    built once, read once from HBM, resident in VMEM for all windows.

    interpret=None auto-selects: the Pallas interpreter on the CPU
    backend (Mosaic needs real hardware), compiled Mosaic elsewhere —
    so the GRAFT_PALLAS backend flip is exercisable on any platform.

    Returns None when no VMEM-safe blocking exists for this width
    (effective_block) rather than build an unbounded VMEM block; there
    ladder_for says ``xla`` and ops/ed25519._straus runs that ladder.
    """
    (n,) = shape
    assert n % 128 == 0, n
    r = n // 128
    s = effective_block(block_sublanes(), r)
    if s is None:
        return None
    if interpret is None:
        interpret = interpret_mode()

    ext = curve.identity(shape)
    entries = [curve.to_cached(ext)]
    acc = ext
    for _ in range(15):
        acc = curve.add(acc, A)
        entries.append(curve.to_cached(acc))
    table = jnp.stack(
        [
            jnp.stack([fe.stack(comp) for comp in e])
            for e in entries
        ]
    )  # (16, 4, 20, N)

    table = table.reshape(16, 4, fe.NLIMBS, r, 128)
    ds_t = ds.reshape(64, r, 128)
    dh_t = dh.reshape(64, r, 128)
    # the EFFECTIVE block, not the configured one: _ladder_call's
    # divisor assert rejects any configured value that doesn't divide
    # r (ADVICE r5 high — N=128 under GRAFT_PALLAS=1 tripped it)
    out = _ladder_call(
        ds_t, dh_t, table,
        block=s, interpret=interpret,
    )
    out = out.reshape(3, fe.NLIMBS, n)
    return (
        tuple(out[0, i] for i in range(fe.NLIMBS)),
        tuple(out[1, i] for i in range(fe.NLIMBS)),
        tuple(out[2, i] for i in range(fe.NLIMBS)),
        None,
    )


def ladder_for(n: int) -> str:
    """``"pallas"`` where ops/ed25519._straus runs ``n`` lanes on this
    kernel, else ``"xla"``: ``n`` a multiple of 128, pallas_enabled(n),
    and a VMEM-safe blocking (effective_block). The one test, shared by
    _straus and the dispatch's ``ladder`` tag, so the tag never says
    pallas where the XLA ladder ran."""
    if n % 128 or not pallas_enabled(n):
        return "xla"
    if effective_block(block_sublanes(), n // 128) is None:
        return "xla"
    return "pallas"
