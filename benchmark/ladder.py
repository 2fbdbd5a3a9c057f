"""Readers for a cell whose verify program runs the Pallas ladder
(ops/pallas_ladder): the VMEM-resident Straus ladder the program takes
by default at bulk widths (``val150.verify-only.512``).

  ladder_ms_per_dispatch   the device time, a run of the verify program,
                           of the ops the trace names after the kernel's
                           jitted call (LADDER_OP): the ladder alone,
                           inside ``kernel_ms_per_dispatch``
  ladder_roofline          the least time the chip could take for the
                           ladder's work on a dispatch's REAL
                           signatures (ladder_int32_ops, ladder_hbm_bytes,
                           peaks.json) over that device time
  pallas_dispatch_share    of the window's dispatches, the share whose
                           ``ops.ed25519.pack`` span says
                           ``ladder=pallas``: 100 where the cell runs as
                           meant, less where the program quietly fell
                           back to the XLA ladder

A record that lacks what a reader reads (a trace with no such op, a
program whose spans carry no ``ladder``, a ring that dropped an event)
gives None, and the harness leaves the metric out of the line.
"""

from __future__ import annotations

from benchmark import opcount, program_spans, record, trace_reduce
from benchmark.live import PACK, window_span_args
from benchmark.probes import say

# the name the device trace gives the kernel's op: the jitted
# ops/pallas_ladder._ladder_call around the pallas_call (``_ladder_call.1``)
LADDER_OP = "_ladder_call"

# --- the ladder's own count -----------------------------------------------------
# opcount's docstring, the ladder's line: 64 windows of 4 doublings, one
# add from the per-lane A table, one mixed add from the constant B
# table, the 16-way selects of both. The A table is built in XLA before
# the call and handed over stacked, so its 15 additions are not the
# kernel's; its bytes, the digit planes and the result point are.


def ladder_int32_ops(signatures: int) -> int:
    point = opcount._point
    selects = (opcount.TABLE - 1) * opcount.LIMBS * (4 + 3)
    window = (
        3 * point(opcount.DOUBLE) + point(opcount.DOUBLE_T)
        + point(opcount.ADD_CACHED) + point(opcount.ADD_MIXED) + selects
    )
    return opcount.WINDOWS * window * signatures


def ladder_hbm_bytes(signatures: int) -> int:
    """The call's inputs and output a lane: the A table (16 entries of
    4 field elements of 20 int32 limbs), two planes of 64 int32 window
    digits, and X, Y, Z of the result."""
    table = opcount.TABLE * 4 * opcount.LIMBS * 4
    digits = 2 * opcount.WINDOWS * 4
    out = 3 * opcount.LIMBS * 4
    return (table + digits + out) * signatures


# --- the device trace -------------------------------------------------------------


def ladder_runs(events: dict) -> list:
    """For each run of the verify program that lies whole inside the
    slice, on every device plane, the seconds of the LADDER_OP ops
    inside it."""
    slices = [r for r in events["host"] if r[0] == trace_reduce.SLICE]
    if not slices:
        return []
    _, t0, dur = slices[0]
    t1 = t0 + dur
    runs = []
    for name in sorted(events["devices"]):
        lines = events["devices"][name]
        ladder = [
            (s, d) for n, s, d in lines.get(trace_reduce.OPS, [])
            if n.startswith(LADDER_OP)
        ]
        for n, s, d in lines.get(trace_reduce.MODULES, []):
            if trace_reduce.KERNEL in n and s >= t0 and s + d <= t1:
                runs.append(
                    sum(ld for ls, ld in ladder if ls >= s and ls + ld <= s + d) / 1e9
                )
    return runs


def mean_ladder_ms(runs: list):
    """``ladder_runs``' rows to the mean ms a run; None where no run
    holds a LADDER_OP op (the XLA ladder ran)."""
    if not runs or not any(runs):
        return None
    return 1e3 * sum(runs) / len(runs)


def ladder_ms_per_dispatch(rec: dict):
    def work():
        if not rec.get("trace"):
            return None
        try:
            path = trace_reduce.find_xplane(program_spans.TRACE_DIR)
        except FileNotFoundError:
            return None
        runs = ladder_runs(trace_reduce.load_events(path))
        ms = mean_ladder_ms(runs)
        if ms is not None:
            say(f"ladder: {len(runs)} whole runs of the verify program; {LADDER_OP} {ms!r} ms a run")
        return ms

    return program_spans._kept(rec, "ladder_ms", work)


def ladder_roofline(rec: dict):
    ms = ladder_ms_per_dispatch(rec)
    rows = rec.get("dispatches")
    if ms is None or not rows:
        return None
    peak = record.peaks(rec["device_kind"])
    sigs = sum(d["sigs"] for d in rows) / len(rows)  # a mean dispatch
    ops_s = ladder_int32_ops(1) * sigs / peak["int32_ops_per_s"]["value"]
    bytes_s = ladder_hbm_bytes(1) * sigs / peak["hbm_bytes_per_s"]["value"]
    say(
        f"ladder: {sigs!r} real signatures a dispatch; least time by "
        f"operations {ops_s!r} s, by bytes {bytes_s!r} s"
    )
    return 100.0 * max(ops_s, bytes_s) / (ms / 1e3)


# --- the program's own tag ----------------------------------------------------------


def pallas_dispatch_share(rec: dict):
    ladders = [a.get("ladder") for a in window_span_args(rec, PACK) or []]
    if not ladders or None in ladders:
        return None  # no dispatch, or a program that does not say
    pallas = ladders.count("pallas")
    say(f"ladder: {pallas} of {len(ladders)} dispatches in the window ran the Pallas ladder")
    return 100.0 * pallas / len(ladders)
