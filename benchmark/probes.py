"""What the harness reads of a run besides the program's own counters:
the device it runs on, compilations, and wraps around the two calls
every cell goes through (the seam and the kernel dispatch), each
written into the profiler's trace as a host annotation.

find_tpu, CompileLog and the dispatch wrap are the benchmark's copies
of chip_smoke.py's (PR 24), which stays as it is.
"""

from __future__ import annotations

import contextlib
import sys
import time


def say(msg: str) -> None:
    print(msg, flush=True)


def find_tpu(chips: int):
    """The ``device`` of the result line, or None (and why, on stderr)
    when JAX finds no TPU or not ``chips`` of them."""
    import jax

    devs = jax.devices()  # raises when the backend cannot start
    if devs[0].platform != "tpu":
        why = f"needs a TPU, JAX found platform {devs[0].platform!r}"
    elif len(devs) != chips:
        why = f"the cell asks for {chips} chips, JAX sees {len(devs)}"
    else:
        return {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
    print(f"benchmark: {why}; nothing was run", file=sys.stderr)
    return None


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, 0 where the backend
    does not say."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileLog:
    """Every program the backend built or loaded from the cache, by
    name and seconds (jax.monitoring), and the persistent-cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        from jax import monitoring

        self.programs: list = []  # (fun_name, seconds)
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_secs)
        monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **kw) -> None:
        if event == self.EVENT:
            self.programs.append((kw.get("fun_name", "?"), secs))

    def _on_event(self, event, **kw) -> None:
        if event == self.HIT:
            self.cache_hits += 1

    def seconds(self) -> float:
        return sum(s for _, s in self.programs)


def annotation(name: str):
    """A span in the profiler's trace, on the device trace's clock."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Probes:
    """Wraps, installed for the life of the run, around

      ops/ed25519.verify_batch_async   every device dispatch: signatures,
                                       LAST_DISPATCH, call-to-return time
      verify_commits_coalesced_async   every call of the seam: commits,
                                       call-to-return time (item building
                                       is synchronous), as the reactor and
                                       the commit stream both reach it

    ``dispatches`` and ``seam_calls`` hold one dict a call, stamped with
    the host's clock so that a window can take its own."""

    def __init__(self) -> None:
        self.dispatches: list = []
        self.seam_calls: list = []
        self._undo: list = []

    def install(self) -> None:
        from cometbft_tpu.blocksync import reactor
        from cometbft_tpu.ops import ed25519 as ed
        from cometbft_tpu.types import validation

        real_dispatch = ed.verify_batch_async

        def dispatch(items):
            t0 = time.perf_counter()
            with annotation("bench.dispatch_prepare"):
                handle = real_dispatch(items)
            t1 = time.perf_counter()
            self.dispatches.append(
                {
                    "t": t0,
                    "prepare_s": t1 - t0,
                    "sigs": len(items),
                    "lanes": ed.LAST_DISPATCH.get("lanes"),
                    "cap": ed.LAST_DISPATCH.get("cap"),
                    "mode": ed.LAST_DISPATCH.get("mode"),
                    "ladder": (ed.LAST_DISPATCH.get("backend_key") or ("?",))[0],
                    "interpret": ed.LAST_DISPATCH.get("interpret"),
                    "n_devices": ed.LAST_DISPATCH.get("n_devices"),
                }
            )
            return handle

        real_seam = validation.verify_commits_coalesced_async

        def seam(chain_id, jobs, *args, **kw):
            t0 = time.perf_counter()
            with annotation("bench.seam"):
                handle = real_seam(chain_id, jobs, *args, **kw)
            self.seam_calls.append(
                {"t": t0, "seam_s": time.perf_counter() - t0, "commits": len(jobs)}
            )
            return handle

        ed.verify_batch_async = dispatch
        validation.verify_commits_coalesced_async = seam
        reactor.verify_commits_coalesced_async = seam
        self._undo = [
            (ed, "verify_batch_async", real_dispatch),
            (validation, "verify_commits_coalesced_async", real_seam),
            (reactor, "verify_commits_coalesced_async", real_seam),
        ]

    def remove(self) -> None:
        for mod, name, real in self._undo:
            setattr(mod, name, real)
        self._undo = []

    @contextlib.contextmanager
    def installed(self):
        self.install()
        try:
            yield self
        finally:
            self.remove()


def between(rows: list, t0: float, t1: float) -> list:
    """The rows stamped inside [t0, t1)."""
    return [r for r in rows if t0 <= r["t"] < t1]
