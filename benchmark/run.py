"""Run one cell of BENCHMARK.json once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. It holds the chip itself, names it in the
result, and exits non-zero with no result line when JAX finds no TPU or
not the cell's chips. Set-up (imports, traffic from the seed, cache load
or compile, tracing, warm-up) is ``setup_s``; then the window; then,
with the program's state freed and the device's peak read, the plain
reference and the comparison that decides ``correct``. A compilation
inside the window fails the run. The last line of stdout is the result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):  # python benchmark/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import lookup  # noqa: E402
from benchmark.probes import (  # noqa: E402
    CompileLog,
    Probes,
    annotation,
    find_tpu,
    memory_peak_bytes,
    say,
)

TRACE_DIR = os.path.join(lookup.ROOT, ".bench_trace")


def traced_slice(traffic, probes, slice_s: float) -> dict:
    """Profile ``slice_s`` more seconds of the same traffic, after the
    window, and reduce the trace (benchmark/trace_reduce.py). The
    profiler is started and stopped from this thread, between windows,
    with nothing on the device: stopped from a thread of its own in the
    middle of the window it took 175-200 s to write a slice of any
    length out, here about 25 s (PERF.md, PR 26)."""
    import shutil

    import jax

    from benchmark import trace_reduce

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the host's own annotations only
    options.enable_hlo_proto = False  # the verify program's is 60 MB
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        with annotation(trace_reduce.SLICE):
            traffic.window(probes, slice_s)
    finally:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"trace: slice of {slice_s!r} s written in {time.perf_counter() - t!r} s")
    t = time.perf_counter()
    path = trace_reduce.find_xplane(TRACE_DIR)
    events = trace_reduce.load_events(path)
    with open(os.path.join(TRACE_DIR, "describe.txt"), "w") as f:
        f.write(trace_reduce.describe(events) + "\n")  # for reading by hand
    reduced = trace_reduce.reduce(events)
    say(
        f"trace: {os.path.getsize(path)} bytes read and reduced in "
        f"{time.perf_counter() - t!r} s"
    )
    return reduced


def slice_seconds(mix: dict, warm_s: float, warm_dispatches: int) -> float:
    """How long the traced slice is: as long as the warm-up took for
    ``trace_slice_dispatches`` device dispatches, ``trace_slice_max_s``
    at the most. A run of the verify program is millions of trace
    events, so the slice is sized in runs; fixed before the window, so
    that where it ends does not follow the traffic."""
    per_dispatch = warm_s / max(1, warm_dispatches)
    return min(
        float(mix["trace_slice_max_s"]),
        mix["trace_slice_dispatches"] * per_dispatch,
    )


def execute(spec, workload, seed, seconds, trace, device, fault=None) -> dict:
    """Everything after the look for a chip; returns the result line.
    ``fault`` is the tests' hook (benchmark/faults.py): called with the
    traffic object after the warm-up, before the window."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.ops import ed25519 as ed
    from cometbft_tpu.utils.device import setup_compile_cache

    cell = lookup.load_cell(spec, workload)
    config, mix = cell["config_data"], cell["mix"]
    cache_dir = setup_compile_cache()
    compiles = CompileLog()
    lookup.apply_pins(config.get("pins", {}))
    crypto_batch.set_default_backend(config["backend"])
    say(
        f"cell {workload}: config={cell['config']} mix={cell['traffic']} seed={seed} "
        f"seconds={seconds} trace={trace} pins={config.get('pins', {})} "
        f"compile cache={cache_dir}"
    )

    # --- set-up ---------------------------------------------------------
    t = time.perf_counter()
    traffic = lookup.load_generator(mix["generator"]).Traffic(config, mix, seed)
    say(f"setup: traffic built in {time.perf_counter() - t!r} s")
    probes = Probes()
    with probes.installed():
        t = time.perf_counter()
        first = ed.verify_batch_async(traffic.warm_items())
        if not first.wait().result().all():
            raise RuntimeError("the warm-up dispatch did not verify")
        warm_first_s = time.perf_counter() - t
        shape = dict(ed.LAST_DISPATCH)
        t = time.perf_counter()
        traffic.warm(probes)
        slice_s = slice_seconds(mix, time.perf_counter() - t, len(probes.dispatches) - 1)
        say(
            f"setup: first dispatch {warm_first_s!r} s (backend compile or cache "
            f"load {compiles.seconds()!r} s, cache_hits={compiles.cache_hits}, "
            f"programs={compiles.programs}); warm-up traffic "
            f"{time.perf_counter() - t!r} s; shape={shape}"
        )
        programs_before = len(compiles.programs)
        if fault is not None:
            fault(traffic)  # under the timed path, the warm-up left sound
        # the benchmark's own traffic (a chain, a pool of commits) is
        # millions of objects that live for the whole run: kept out of
        # the collector's later passes, so that hosting the generator in
        # the program's process does not tax the program's window
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - _T_START

        # --- the window -------------------------------------------------
        t = time.perf_counter()
        traffic.window(probes, float(seconds))
        say(f"window: closed after {time.perf_counter() - t!r} s")
        record = traffic.record
        attempted, failed = traffic.counts()
        end_to_end = dict(traffic.end_to_end(), setup_s=setup_s)
        # a traced run goes on with the same traffic for a slice more,
        # under the profiler; its answers are compared with the window's
        record["trace"] = traced_slice(traffic, probes, slice_s) if trace else None
    compiled = compiles.programs[programs_before:]
    record["setup"] = {
        "setup_s": setup_s,
        "warm_first_s": warm_first_s,
        "backend_compile_s": sum(s for _, s in compiles.programs[:programs_before]),
        "cache_hits": compiles.cache_hits,
    }
    record["device_kind"] = device["kind"]
    record["shape"] = shape
    device = dict(device, memory_peak_bytes=memory_peak_bytes())

    # --- correct: the reference runs last -------------------------------
    t = time.perf_counter()
    numbers = traffic.compare()
    numbers.append(("compilations_in_window", float(len(compiled)), 0.0))
    numbers.append(("commits_failed", float(failed), 0.0))
    traffic.free()
    say(f"compare: {time.perf_counter() - t!r} s")

    # --- metrics --------------------------------------------------------
    if record["trace"]:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    metrics = {}
    if trace:
        for m in lookup.metrics_for(spec, workload, "per_layer"):
            value = lookup.load_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in lookup.metrics_for(spec, workload, "end_to_end"):
            metrics[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
    correct = all(value <= limit for _, value, limit in numbers)
    compared = {name: {"value": value, "limit": limit} for name, value, limit in numbers}
    if compiled:
        say(f"window compiled {compiled}")
    result = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if record["trace"]:
        result["breakdown"] = record["trace"]["breakdown"]
    result["workload"] = workload
    result["seed"] = seed
    result["compared"] = compared
    for name, value, limit in numbers:
        print(f"compared {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = lookup.load_spec()
    cell = lookup.by_name(spec["workloads"], args.workload, "workload")
    device = find_tpu(cell["chips"])
    if device is None:
        return 2
    result = execute(spec, args.workload, args.seed, args.seconds, bool(args.trace), device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.exit(rc)
