"""The program's own spans, read two ways for the per-layer readers.

The verify path records one span a stage of a ticket on the process
tracer (cometbft_tpu/trace, docs/TRACE.md "One ticket, one timeline"),
and the live ones also as ``jax.profiler.TraceAnnotation``s, so that a
profiler session holds them beside the device's events. From them:

  window(record)   the process tracer's ``X`` events whose start lies
                   inside the timed window, by name and by ticket. The
                   window's ends are the first and the last stamp the
                   record holds (``seam_calls[*].t``, ``dispatches[*].t``:
                   ``time.perf_counter()``, the tracer's clock on Linux).
                   Nothing where the ring has dropped an event.
  slice_gaps(record)
                   the traced slice's idle gaps, each piece named by the
                   program span the host was in then: the xplane's host
                   events whose names are the program's, put through
                   trace_reduce._host_doing in place of the ``bench.*``
                   rows, over trace_reduce.load_events' device rows.

A program that records no such span (the parent of the PR that added
them) gives nothing to read, and every reader here returns None.
"""

from __future__ import annotations

import os
import time

from benchmark import lookup, trace_reduce
from benchmark.probes import say
from benchmark.record import span_seconds

TRACE_DIR = os.path.join(lookup.ROOT, ".bench_trace")  # as run.py names it
PREFIXES = ("blocksync.", "validation.", "crypto.sched.", "ops.ed25519.")

BUILD = "validation.coalesce.build"
FOLD = "validation.coalesce.fold"
RESOLVE = "crypto.sched.resolve"
# a ticket's stages from the seam's entry to its verdicts, in order
STAGES = (
    BUILD,
    "crypto.sched.queue_wait",
    "crypto.sched.route",
    "ops.ed25519.pack",
    "ops.ed25519.enqueue",
    "crypto.sched.device_wait",
    RESOLVE,
)
LIFE = STAGES + (FOLD,)  # submit to verdicts, as the caller sees a batch

# the record's stamps are time.perf_counter(), the ring's time.monotonic_ns():
# one clock on Linux. Where they are not, the ring has no window to give.
CLOCKS_AGREE = abs(time.perf_counter() - time.monotonic_ns() / 1e9) < 1e-3

NO_SPAN = "no program span"  # the idle time no program span covers
_KEY = "_program_spans"  # what was worked out, kept on the record


def _kept(record: dict, what: str, work):
    kept = record.setdefault(_KEY, {})
    if what not in kept:
        kept[what] = work()
    return kept[what]


# --- the window, from the ring ---------------------------------------------


def window_bounds(record: dict):
    """(first, last) stamp of the window in ns, or None."""
    stamps = [r["t"] for k in ("seam_calls", "dispatches") for r in record.get(k) or []]
    if not stamps or not CLOCKS_AGREE:
        return None
    return int(min(stamps) * 1e9), int(max(stamps) * 1e9)


def ring_window(events: list, t0_ns: int, t1_ns: int) -> dict:
    """``events`` (a tracer's snapshot) cut to the complete spans whose
    start lies in [t0_ns, t1_ns]: {"by_name": {name: [dur_s]},
    "tickets": {id: {name: (start_ns, dur_ns)}}}."""
    by_name: dict = {}
    tickets: dict = {}
    for e in events:
        if e["ph"] != "X" or not t0_ns <= e["ts_ns"] <= t1_ns:
            continue
        by_name.setdefault(e["name"], []).append(e["dur_ns"] / 1e9)
        ticket = e["args"].get("ticket")
        if ticket is not None:
            tickets.setdefault(ticket, {})[e["name"]] = (e["ts_ns"], e["dur_ns"])
    return {"by_name": by_name, "tickets": tickets}


def window(record: dict):
    def work():
        from cometbft_tpu.trace import global_tracer

        bounds = window_bounds(record)
        tracer = global_tracer()
        if bounds is None or not tracer.enabled:
            return None
        dropped = tracer.stats()["dropped"]
        if dropped:
            say(f"program spans: the ring dropped {dropped} events; nothing is read")
            return None
        view = ring_window(tracer.snapshot(), *bounds)
        _say_stages(view, record.get("batch_s"))
        return view

    return _kept(record, "window", work)


def _say_stages(view: dict, batch_s=None) -> None:
    """The per-stage split, for PERF.md; with the window's own
    submit-to-verdicts times (``batch_s``), the stage means' sum
    beside their mean."""
    means = {}
    for name in LIFE + ("crypto.sched.dispatch",):
        rows = view["by_name"].get(name)
        if rows:
            means[name] = sum(rows) / len(rows)
            say(
                f"program spans: {name} x{len(rows)} mean "
                f"{1e3 * means[name]!r} ms, {sum(rows)!r} s in all"
            )
    if batch_s and all(s in means for s in LIFE):
        total = sum(means[s] for s in LIFE)
        life = sum(batch_s) / len(batch_s)
        say(
            f"program spans: the eight stage means sum to {1e3 * total!r} ms; "
            f"a batch of the window lived {1e3 * life!r} ms from submit to "
            f"verdicts on the caller's clock ({100.0 * total / life!r} %)"
        )


def mean_ms(record: dict, name: str):
    """Mean length of the window's spans called ``name``, ms."""
    view = window(record)
    rows = view["by_name"].get(name) if view else None
    if not rows:
        return None
    return 1e3 * sum(rows) / len(rows)


def _mean_of(name: str):
    def read(record: dict):
        return mean_ms(record, name)

    return read


# one reader a stage (benchmark/metrics/<metric>.<verify|catchup>.py)
seam_build_ms_per_batch = _mean_of(BUILD)
seam_fold_ms_per_batch = _mean_of(FOLD)
sched_queue_wait_ms = _mean_of("crypto.sched.queue_wait")
route_ms_per_ticket = _mean_of("crypto.sched.route")
resolve_ms_per_ticket = _mean_of(RESOLVE)
pack_ms_per_dispatch = _mean_of("ops.ed25519.pack")
enqueue_ms_per_dispatch = _mean_of("ops.ed25519.enqueue")
device_wait_ms_per_dispatch = _mean_of("crypto.sched.device_wait")


def unaccounted(tickets: dict):
    """(share %, seconds no stage covers, tickets counted): per ticket
    that left every stage, 1 - sum of the stages over the time from
    build's start to resolve's end; the mean over those tickets."""
    shares = []
    dark_s = 0.0
    for spans in tickets.values():
        if any(s not in spans for s in STAGES):
            continue
        life = spans[RESOLVE][0] + spans[RESOLVE][1] - spans[BUILD][0]
        if life <= 0:
            continue
        covered = sum(spans[s][1] for s in STAGES)
        shares.append(1.0 - covered / life)
        dark_s += (life - covered) / 1e9
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares), dark_s, len(shares)


def ticket_unaccounted_share(record: dict):
    view = window(record)
    got = unaccounted(view["tickets"]) if view else None
    if got is None:
        return None
    share, dark_s, n = got
    say(
        f"program spans: {n} whole tickets in the window; no stage span "
        f"covers {dark_s!r} s of their lives ({share!r} %)"
    )
    return share


# --- the blocksync window's pair, from the record's spans --------------------


def fetch_wait_share(record: dict):
    waited = span_seconds(record, "blocksync.window.fetch_wait")
    if waited is None or not record.get("window_s"):
        return None
    return 100.0 * waited / record["window_s"]


def window_prepare_ms_per_block(record: dict):
    prepared = span_seconds(record, "blocksync.window.prepare")
    blocks = record.get("blocks_applied")
    if prepared is None or not blocks:
        return None
    return 1e3 * prepared / blocks


# --- the slice, from the xplane -----------------------------------------------


def load_program_rows(path: str) -> list:
    """(name, start_ns, dur_ns) of the host planes' events that carry
    one of the program's names."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            rows += [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
                if e.name.startswith(PREFIXES)
            ]
    return rows


def gaps_by_program_span(events: dict, program_rows: list):
    """The slice's idle gaps by program span, seconds, a device's mean:
    trace_reduce.reduce's walk over the device's busy intervals, with
    the program's rows where it takes the benchmark's own. None where
    the trace holds no slice or no device."""
    slices = [r for r in events["host"] if r[0] == trace_reduce.SLICE]
    if not slices or not events["devices"]:
        return None
    _, t0, dur = slices[0]
    t1 = t0 + dur
    rows = [r for r in program_rows if r[1] < t1 and r[1] + r[2] > t0]
    gaps: dict = {}
    for name in sorted(events["devices"]):
        lines = events["devices"][name]
        busy = lines.get(trace_reduce.OPS) or lines.get(trace_reduce.MODULES, [])
        merged = trace_reduce._union(trace_reduce._clip(busy, t0, t1))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                trace_reduce._host_doing(rows, g0, g1, gaps)
    if trace_reduce.IDLE_OTHER in gaps:
        gaps[NO_SPAN] = gaps.pop(trace_reduce.IDLE_OTHER)
    n_dev = len(events["devices"])
    return {k: v / n_dev for k, v in gaps.items()}


def slice_gaps(record: dict):
    def work():
        if not record.get("trace"):
            return None
        try:
            path = trace_reduce.find_xplane(TRACE_DIR)
        except FileNotFoundError:
            return None
        rows = load_program_rows(path)
        if not rows:
            return None  # a program that annotates nothing
        gaps = gaps_by_program_span(trace_reduce.load_events(path), rows)
        if gaps:
            seen: dict = {}
            for n, _, d in rows:
                seen.setdefault(n, []).append(d / 1e9)
            for n, durs in sorted(seen.items()):
                say(
                    f"program spans: xplane holds {n} x{len(durs)} {sum(durs)!r} s "
                    f"(shortest {min(durs)!r}, longest {max(durs)!r})"
                )
            for n, sec in sorted(gaps.items(), key=lambda kv: -kv[1]):
                say(f"program spans: idle gap under {n}: {sec!r} s")
        return gaps

    return _kept(record, "slice_gaps", work)


def unattributed(gaps: dict):
    """(share %, seconds under no program span, idle seconds)."""
    idle = sum(gaps.values())
    if idle <= 0:
        return None
    dark = gaps.get(NO_SPAN, 0.0)
    return 100.0 * dark / idle, dark, idle


def idle_unattributed_share(record: dict):
    gaps = slice_gaps(record)
    got = unattributed(gaps) if gaps else None
    if got is None:
        return None
    share, dark, idle = got
    say(
        f"program spans: of {idle!r} s the device idled in the slice, "
        f"{dark!r} s lie under no program span ({share!r} %)"
    )
    return share
