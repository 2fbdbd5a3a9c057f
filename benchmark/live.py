"""Readers for a cell of single commits verified in full, open loop
(``val150.commit-live``): one verify_commit a request, one scheduler
ticket of the set's size, one dispatch of the ``precomp`` kernel form
where the router says device.

  commit_build / commit_fold   the program's own spans around the two
                               stages of verify_commit on the caller's
                               thread (``validation.commit.build``,
                               ``.fold``), mean over the window
  precomp_kernel_roofline      record.kernel_roofline's arithmetic with
                               the ``precomp`` form's count: the key
                               arrives expanded, so ONE square-root
                               chain (R's) and 320 bytes more a lane
  key_expand_miss_share        of the distinct keys the window's
                               ``precomp`` dispatches saw, the share the
                               expanded-key LRU did not hold (the
                               ``keys`` / ``expanded`` args of
                               ``ops.ed25519.pack``)
  send_lag                     how late the open-loop generator ran:
                               sent minus due, 99th percentile

A record that lacks what a reader reads (another generator's, a program
without the spans or the args, a ring that dropped an event) gives
None, and the harness leaves the metric out of the line.
"""

from __future__ import annotations

from benchmark import lookup, opcount, program_spans, record
from benchmark.probes import say

BUILD = "validation.commit.build"
FOLD = "validation.commit.fold"
PACK = "ops.ed25519.pack"


def commit_build_ms(rec: dict):
    return program_spans.mean_ms(rec, BUILD)


def commit_fold_ms(rec: dict):
    return program_spans.mean_ms(rec, FOLD)


# --- the precomp form's count -------------------------------------------------
# ops/ed25519._verify_core_precomp's docstring: A arrives in
# affine-extended limb form (x, y, 1, x*y), 4 x 20 int32 limbs a lane,
# expanded once a distinct key on the host, so only R pays the
# square-root chain; the key's 32 bytes still go in (the hash is over
# R || A_bytes || M). Everything else is the plain form's algorithm.

A_LIMB_BYTES = 4 * opcount.LIMBS * 4


def precomp_int32_ops(cap: int, signatures: int) -> int:
    chain = opcount.SQRT_CHAIN[0] * opcount.FE_MUL + opcount.SQRT_CHAIN[1] * opcount.FE_SQ
    return opcount.int32_ops(cap, signatures) - chain * signatures


def precomp_hbm_bytes(cap: int, signatures: int) -> int:
    return opcount.hbm_bytes(cap, signatures) + A_LIMB_BYTES * signatures


def precomp_kernel_roofline(rec: dict):
    """The least time the chip could take for the REAL signatures of a
    mean ``precomp`` dispatch over the kernel time the trace shows:
    the 106 padding lanes of a 150-signature commit lower it."""
    t = rec.get("trace")
    rows = rec.get("dispatches")
    if not t or not t["kernel_runs_s"] or not rows:
        return None
    if any(d.get("mode") != "precomp" for d in rows):
        return None  # another kernel form: record.kernel_roofline's
    peak = record.peaks(rec["device_kind"])
    sigs = sum(d["sigs"] for d in rows) / len(rows)
    cap = rows[0]["cap"]
    ops_s = precomp_int32_ops(cap, 1) * sigs / peak["int32_ops_per_s"]["value"]
    bytes_s = precomp_hbm_bytes(cap, 1) * sigs / peak["hbm_bytes_per_s"]["value"]
    kernel_s = sum(t["kernel_runs_s"]) / len(t["kernel_runs_s"])
    say(
        f"live: precomp kernel {1e3 * kernel_s!r} ms a run over "
        f"{len(t['kernel_runs_s'])} runs; {sigs!r} real signatures a dispatch; "
        f"least time by operations {ops_s!r} s, by bytes {bytes_s!r} s"
    )
    return 100.0 * max(ops_s, bytes_s) / kernel_s


# --- the expanded-key LRU ---------------------------------------------------------


def window_span_args(rec: dict, name: str):
    """The args of the process tracer's spans called ``name`` that
    start inside the window (program_spans.window_bounds), or None
    where the ring cannot say (off, an event dropped, no window)."""
    from cometbft_tpu.trace import global_tracer

    bounds = program_spans.window_bounds(rec)
    tracer = global_tracer()
    if bounds is None or not tracer.enabled or tracer.stats()["dropped"]:
        return None
    t0, t1 = bounds
    return [
        e["args"] for e in tracer.snapshot()
        if e["ph"] == "X" and e["name"] == name and t0 <= e["ts_ns"] <= t1
    ]


def key_expand_miss_share(rec: dict):
    rows = window_span_args(rec, PACK)
    rows = [a for a in rows or [] if "keys" in a]
    keys = sum(a["keys"] for a in rows)
    if not keys:
        return None
    expanded = sum(a["expanded"] for a in rows)
    say(
        f"live: {len(rows)} precomp dispatches in the window saw {keys} "
        f"distinct keys and expanded {expanded}"
    )
    return 100.0 * expanded / keys


# --- the generator's own lateness -----------------------------------------------


def send_lag_ms(rec: dict):
    rows = rec.get("requests")
    if not rows:
        return None
    lag = sorted(r["sent"] - r["due"] for r in rows)
    return 1e3 * lookup.percentile(lag, 99)
