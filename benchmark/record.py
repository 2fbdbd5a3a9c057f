"""Arithmetic the per-layer readers share, over a run's record.

The record is what a generator's window leaves: ``window_s``, ``spans``
(the program's blocksync.window.* spans), ``pipeline``, ``sched`` (the
scheduler's counters over the window), ``dispatches`` and ``seam_calls``
(the probes' wraps), ``setup`` and, in a traced run, ``trace`` (the
reduced device trace). A reader that finds nothing to read returns None
and the harness leaves the metric out of the line.
"""

from __future__ import annotations

import json
import os

from benchmark import opcount

_HERE = os.path.dirname(os.path.abspath(__file__))


def span_seconds(record: dict, *names: str):
    rows = [s["dur_s"] for s in record.get("spans", []) if s["name"] in names]
    return sum(rows) if rows else None


def verify_wait_share(record: dict):
    waited = span_seconds(record, "blocksync.window.verify_wait")
    if waited is None:
        return None
    return 100.0 * waited / record["window_s"]


def lookahead_reuse(record: dict):
    p = record.get("pipeline")
    if not p or not p["predispatched"]:
        return None  # one window a join: nothing to look ahead to
    windows = p["reused"] + p["dispatched"]
    return 100.0 * p["reused"] / windows if windows else None


def apply_ms_per_block(record: dict):
    busy = span_seconds(
        record, "blocksync.window.apply", "blocksync.window.persist"
    )
    blocks = record.get("blocks_applied")
    if busy is None or not blocks:
        return None
    return 1e3 * busy / blocks


def seam_ms_per_batch(record: dict):
    calls = record.get("seam_calls")
    if not calls:
        return None
    return 1e3 * sum(c["seam_s"] for c in calls) / len(calls)


def device_sig_share(record: dict):
    lanes = record["sched"]["lanes"]
    if not lanes:
        return None
    return 100.0 * sum(d["sigs"] for d in record["dispatches"]) / lanes


def degraded_dispatches(record: dict):
    return float(record["sched"]["degraded"])


def prepare_ms_per_dispatch(record: dict):
    rows = record["dispatches"]
    if not rows:
        return None
    return 1e3 * sum(d["prepare_s"] for d in rows) / len(rows)


def lane_fill(record: dict):
    rows = record["dispatches"]
    if not rows:
        return None
    return 100.0 * sum(d["sigs"] for d in rows) / sum(d["lanes"] for d in rows)


def kernel_ms_per_dispatch(record: dict):
    t = record.get("trace")
    if not t or not t["kernel_runs_s"]:
        return None
    return 1e3 * sum(t["kernel_runs_s"]) / len(t["kernel_runs_s"])


def device_idle_share(record: dict):
    t = record.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"peaks.json has no device kind {device_kind!r}")
    return table[device_kind]


def kernel_roofline(record: dict):
    """The least time the chip could take for the REAL signatures of the
    dispatches, over the kernel time the trace shows for as many runs:
    padding, and whatever the kernel does beyond the count, lower it."""
    t = record.get("trace")
    rows = record["dispatches"]
    if not t or not t["kernel_runs_s"] or not rows:
        return None
    peak = peaks(record["device_kind"])
    sigs = sum(d["sigs"] for d in rows) / len(rows)  # a mean dispatch
    cap = rows[0]["cap"]
    ops_s = opcount.int32_ops(cap, 1) * sigs / peak["int32_ops_per_s"]["value"]
    bytes_s = opcount.hbm_bytes(cap, 1) * sigs / peak["hbm_bytes_per_s"]["value"]
    kernel_s = sum(t["kernel_runs_s"]) / len(t["kernel_runs_s"])
    record["roofline_bound"] = "compute" if ops_s >= bytes_s else "memory"
    return 100.0 * max(ops_s, bytes_s) / kernel_s


def trace_lower_s(record: dict):
    s = record["setup"]
    return s["warm_first_s"] - s["backend_compile_s"]
