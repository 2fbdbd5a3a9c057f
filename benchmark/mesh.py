"""Readers for a cell whose verify dispatch is lane-sharded over
several devices (``chips`` > 1): one dispatch's lanes split over the
devices of a host, each running the verify program on its own shard.

  mesh_kernel_roofline     record.kernel_roofline's share against the
                           peak of ALL the dispatch's devices: a
                           dispatch's signatures are verified by
                           ``n_devices`` chips, each for the mean
                           per-device kernel time the trace shows
  mesh_put / mesh_fetch    the program's own child spans around the
                           placement of a dispatch's host arrays on its
                           devices and the read of its verdicts back
  mesh_shard_skew          per dispatch, how far apart the devices end
                           their runs of the verify program: since each
                           runs the same lanes in the same time, how far
                           apart the host started them

A program that records no such span, or a trace with one device plane,
gives nothing to read, and the reader returns None.
"""

from __future__ import annotations

from benchmark import program_spans, record, trace_reduce
from benchmark.probes import say


def n_devices(rec: dict):
    rows = rec.get("dispatches")
    return rows[0].get("n_devices") if rows else None


def mesh_kernel_roofline(rec: dict):
    n = n_devices(rec)
    one_chip = record.kernel_roofline(rec) if n else None
    return None if one_chip is None else one_chip / n


def mesh_put_ms_per_dispatch(rec: dict):
    return program_spans.mean_ms(rec, "ops.ed25519.put")


def mesh_fetch_ms_per_dispatch(rec: dict):
    return program_spans.mean_ms(rec, "ops.ed25519.fetch")


def kernel_ends(events: dict) -> list:
    """For each device plane, sorted by name, the end (ns) of every run
    of the verify program that lies whole inside the slice, in order."""
    slices = [r for r in events["host"] if r[0] == trace_reduce.SLICE]
    if not slices:
        return []
    _, t0, dur = slices[0]
    t1 = t0 + dur
    return [
        sorted(
            s + d
            for n, s, d in events["devices"][name].get(trace_reduce.MODULES, [])
            if trace_reduce.KERNEL in n and s >= t0 and s + d <= t1
        )
        for name in sorted(events["devices"])
    ]


def shard_skew_ms(ends: list):
    """``kernel_ends``' rows to the mean over the slice's dispatches of
    (last device's end - first device's end), ms: the k-th run of each
    plane is the k-th dispatch's shard there. None with fewer than two
    device planes or no whole run."""
    runs = min(map(len, ends)) if len(ends) > 1 else 0
    if not runs:
        return None
    skews = [
        max(e[k] for e in ends) - min(e[k] for e in ends) for k in range(runs)
    ]
    return sum(skews) / len(skews) / 1e6


def mesh_shard_skew(rec: dict):
    if not rec.get("trace"):
        return None
    try:
        path = trace_reduce.find_xplane(program_spans.TRACE_DIR)
    except FileNotFoundError:
        return None
    ends = kernel_ends(trace_reduce.load_events(path))
    skew = shard_skew_ms(ends)
    if skew is not None:
        say(
            f"mesh: verify program runs a device plane {[len(e) for e in ends]}; "
            f"mean shard skew {skew!r} ms"
        )
    return skew
