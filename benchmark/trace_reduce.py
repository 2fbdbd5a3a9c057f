"""From the profiler's .xplane.pb to the numbers the readers take.

Two steps, so that the second can be checked on a small recorded trace
(benchmark/testdata/trace_small.json.gz, benchmark/selfcheck.py):

  load_events(path)   the xplane's events as plain rows: for each device
                      plane the ``XLA Modules`` and ``XLA Ops`` lines as
                      (name, start_ns, dur_ns), only the events that no
                      other encloses, and the host's ``bench.*``
                      annotations
  reduce(events)      busy_s (union of the device's busy intervals inside
                      the slice, averaged over the device planes),
                      window_s (the ``bench.slice`` annotation), one
                      duration a run of the verify program, the device
                      operations that took most time, and the longest
                      idle gaps, each piece named by the annotation the
                      host was in then

A device plane is one whose name starts with ``/device:TPU``. Its
``XLA Modules`` line holds one event a run of a jitted program (named
after it, so ``verify_core`` finds the verify program after a refactor
that keeps the name); its ``XLA Ops`` line holds the program's single
operations. Busy time is the union over the ops where the plane has
them, else over the modules.
"""

from __future__ import annotations

import glob
import os

KERNEL = "verify_core"
DEVICE_PREFIX = "/device:TPU"
MODULES = "XLA Modules"
OPS = "XLA Ops"
SLICE = "bench.slice"
HOST_PREFIX = "bench."
IDLE_OTHER = "host: outside the benchmark's annotations"


def find_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An XLA op's event is named by its whole HLO line; the name
    before `` = `` is what tells ops apart."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def top_level(events) -> list:
    """(name, start_ns, dur_ns) of the events no other event of the line
    encloses. A line's events come sorted by start; the verify program's
    loops enclose an event for every operation of every iteration,
    millions a second, and their union is the loops' own spans."""
    rows = []
    end = -1.0
    for e in events:
        s = float(e.start_ns)
        d = float(e.duration_ns)
        if s + d <= end:
            continue
        rows.append((short_name(e.name), s, d))
        end = s + d
    return rows


def load_events(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = {}
    host = []
    planes = []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PREFIX)
        lines = {}
        for line in plane.lines:
            if on_device and line.name in (MODULES, OPS):
                rows = top_level(line.events)
                lines.setdefault(line.name, []).extend(rows)
                planes.append((plane.name, line.name, len(rows)))
            elif not on_device:
                rows = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)
                ]
                host.extend(rows)
                if rows:
                    planes.append((plane.name, line.name, len(rows)))
            else:
                planes.append((plane.name, line.name, -1))  # not read
        if on_device:
            devices[plane.name] = lines
    return {"devices": devices, "host": host, "planes": planes}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(rows: list, t0: float, t1: float) -> list:
    return [
        (max(s, t0), min(s + d, t1)) for _, s, d in rows if s < t1 and s + d > t0
    ]


def reduce(events: dict) -> dict:
    slices = [r for r in events["host"] if r[0] == SLICE]
    if not slices:
        raise ValueError("the trace holds no bench.slice annotation")
    _, t0, dur = slices[0]
    t1 = t0 + dur
    busy = []
    kernel_runs = []
    op_seconds: dict = {}
    gaps: dict = {}
    host = [r for r in events["host"] if r[0] != SLICE]
    for name in sorted(events["devices"]):
        lines = events["devices"][name]
        modules = lines.get(MODULES, [])
        ops = lines.get(OPS, [])
        merged = _union(_clip(ops or modules, t0, t1))
        busy.append(sum(e - s for s, e in merged) / 1e9)
        # a run counts where it lies whole inside the slice
        kernel_runs += [
            d / 1e9 for n, s, d in modules if KERNEL in n and s >= t0 and s + d <= t1
        ]
        for n, s, d in ops or modules:
            if s < t1 and s + d > t0:
                op_seconds[n] = op_seconds.get(n, 0.0) + (min(s + d, t1) - max(s, t0)) / 1e9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                _host_doing(host, g0, g1, gaps)
    if not busy:
        raise ValueError("the trace holds no device plane")
    top = lambda d: [  # noqa: E731
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]
    ]
    n_dev = len(busy)
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": dur / 1e9,
        "kernel_runs_s": kernel_runs,
        "breakdown": {
            "device_ops": top(op_seconds),
            "idle_gaps": top({k: v / n_dev for k, v in gaps.items()}),
        },
    }


def _host_doing(host: list, g0: float, g1: float, gaps: dict) -> None:
    """Add the idle gap [g0, g1) to ``gaps``, each piece of it under the
    annotation the host was in then. Where several cover a piece (they
    nest, or lie on different threads), one that says a thread works
    takes it before one that says a thread waits (``*_wait``), and of
    those the one begun last."""
    inside = [r for r in host if r[1] < g1 and r[1] + r[2] > g0]
    cuts = sorted({g0, g1} | {x for _, s, d in inside for x in (s, s + d) if g0 < x < g1})
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        covering = [
            (n.endswith("_wait"), -s, n) for n, s, d in inside if s <= mid < s + d
        ]
        what = min(covering)[2] if covering else IDLE_OTHER
        gaps[what] = gaps.get(what, 0.0) + (b - a) / 1e9


def describe(events: dict) -> str:
    """The planes and lines of a trace, for reading one by hand."""
    out = [f"{p} | {ln} | {n} events" for p, ln, n in events["planes"]]
    for name, lines in events["devices"].items():
        for ln, rows in lines.items():
            names: dict = {}
            for n, _, d in rows:
                c = names.setdefault(n, [0, 0.0])
                c[0] += 1
                c[1] += d / 1e9
            for n, (c, sec) in sorted(names.items(), key=lambda kv: -kv[1][1])[:12]:
                out.append(f"  {name} | {ln} | {n[:80]} x{c} {sec:.6f} s")
    seen: dict = {}
    for n, _, d in events["host"]:
        c = seen.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += d / 1e9
    out += [f"  host | {n} x{c} {sec:.6f} s" for n, (c, sec) in seen.items()]
    return "\n".join(out)
