"""Multi-core host verification plane (docs/PERF.md §"Host verification
plane").

With no reachable accelerator the *host* pipeline is the hardware, and
the round-5 profile puts serial OpenSSL ed25519 verify at ~2/3 of the
replay wall (16.5 s of 25.8 s per 1500 blocks) on ONE core while the
rest idle. Signature verification dominating committee-based consensus
wall-clock is exactly the finding of "Performance of EdDSA and BLS
Signatures in Committee-Based Consensus" (arXiv 2302.00418); this
module is the host-side analog of that paper's dedicated verification
engine: verification lanes fan out in chunks over a persistent worker
pool, per-lane verdicts merge back in input order.

Tier selection follows the crypto dependency gate (crypto/_ossl.py):

- **thread tier** — when ed25519 verification reaches OpenSSL (the
  ``cryptography`` wheel or the ctypes ``_ossl`` bindings): both
  release the GIL for the duration of each EVP call, so plain threads
  scale with cores and the items never need pickling.
- **process tier** — when only the pure-Python reference
  implementation is available (it holds the GIL throughout): chunks
  are shipped to a process pool instead. Items are plain picklable
  tuples of frozen-dataclass keys and bytes.
- **serial tier** — pool creation failed (restricted container) or
  one usable core: verify on the calling thread, bit-identically.

Chunk size is auto-calibrated like crypto/batch.py's dispatch
calibration: a small benchmark at pool init measures the serial
per-item cost, chunk walls observed from real batches keep an EWMA of
it, and chunks are sized so each one amortizes the submit/merge
overhead (~target_ms of work) while still giving every worker a share
of mid-size batches.

Env knobs (all optional):
  GRAFT_VERIFY_WORKERS         worker count (default: os.cpu_count(), capped)
  GRAFT_VERIFY_TIER            thread | process | serial (force a tier)
  GRAFT_VERIFY_CHUNK_TARGET_MS per-chunk wall target (default 4.0)
  GRAFT_VERIFY_MIN_PARALLEL    batch size below which verify is serial
"""

from __future__ import annotations

import os
import threading
import time
from typing import List, Optional, Sequence, Tuple

from ..trace import global_tracer

_MAX_WORKERS_CAP = 16
_MIN_CHUNK = 8
_DEFAULT_MIN_PARALLEL = 24
_DEFAULT_CHUNK_TARGET_S = 4e-3
_EWMA_ALPHA = 0.3


def _ed25519_releases_gil() -> bool:
    """True when ed25519 verification reaches OpenSSL (wheel or ctypes
    bindings) — both release the GIL during the EVP call, so the
    thread tier scales on cores. Pure-Python fallback holds the GIL
    throughout; the process tier is the only way to spread it."""
    from . import keys

    return bool(keys._HAVE_OSSL or keys._HAVE_CTYPES_OSSL)


def _disable_worker_tracing() -> None:
    """Process-pool child initializer: a fork-started worker inherits
    the parent's enabled process tracer, but its ring can never be
    read (it lives in the child) — keep the chunk path no-op there."""
    from ..trace import enable_global

    enable_global(False)


def _verify_chunk(items, tier: str = "?") -> Tuple[List[bool], float]:
    """Worker body (top-level so the process tier can pickle it):
    verify one chunk, returning (verdicts, serial wall) — the wall
    feeds the per-item EWMA that sizes future chunks.

    Fast path: the native extension (crypto/native_verify) verifies
    the whole chunk in ONE GIL-releasing call — the per-lane ctypes
    transitions otherwise convoy worker threads on the GIL and cap
    thread-tier scaling well below the core count. Fallback (no
    compiler / disabled): the bit-identical per-lane Python loop.

    Traced onto the process-wide ring (trace/global_tracer) with
    worker id + lane count + tier: worker subprocesses never enable
    the global tracer, so the process tier's children stay no-op and
    only the thread tier (shared ring) records chunk spans."""
    tr = global_tracer()
    sp = (
        tr.span(
            "crypto.verify_chunk",
            tid=threading.current_thread().name,
            lanes=len(items),
            tier=tier,
        )
        if tr.enabled
        else None
    )
    t0 = time.perf_counter()
    try:
        from . import native_verify

        oks = native_verify.verify_chunk(items)
    except Exception:  # pragma: no cover - defensive: never lose lanes
        oks = None
    if oks is None:
        oks = [pk.verify(msg, sig) for pk, msg, sig in items]
    wall = time.perf_counter() - t0
    if sp is not None:
        sp.end()
    return oks, wall


class PendingLanes:
    """In-flight parallel verify: per-lane verdicts behind a blocking
    ``result()``, merged back in input order. ``wall()`` reports the
    dispatch→completion wall recorded by the LAST chunk's done
    callback — immune to how long the caller overlaps host work
    before resolving (the same poisoning concern as the device
    calibration watcher, crypto/scheduler.py)."""

    __slots__ = (
        "_futures", "_engine", "_n", "_t0", "_done_t", "_left", "_lock",
    )

    def __init__(self, futures, engine, n: int) -> None:
        self._futures = futures  # [(start, future)]
        self._engine = engine
        self._n = n
        self._t0 = time.perf_counter()
        self._done_t: Optional[float] = None
        self._left = len(futures)
        self._lock = threading.Lock()
        for _, fut in futures:
            fut.add_done_callback(self._one_done)

    def _one_done(self, _fut) -> None:
        self._engine._chunk_done()
        with self._lock:
            self._left -= 1
            if self._left == 0:
                self._done_t = time.perf_counter()

    def wall(self) -> Optional[float]:
        """Dispatch→last-chunk-completion wall, or None while pending."""
        with self._lock:
            done = self._done_t
        return None if done is None else done - self._t0

    def result(self) -> List[bool]:
        oks: List[bool] = [False] * self._n
        for start, fut in self._futures:
            chunk_oks, chunk_wall = fut.result()
            oks[start : start + len(chunk_oks)] = chunk_oks
            self._engine._observe_chunk(len(chunk_oks), chunk_wall)
        with self._lock:
            if self._done_t is None:
                # futures notify waiters BEFORE running done
                # callbacks, so result() can unblock a beat before
                # the last _one_done fires; all work is done at this
                # point, so stamping now keeps wall() available to
                # the host-cost EWMA instead of dropping the sample
                self._done_t = time.perf_counter()
        return oks


class _ResolvedLanes:
    """Already-computed verdicts behind the PendingLanes interface
    (serial path / empty batch)."""

    __slots__ = ("_oks", "_wall")

    def __init__(self, oks: List[bool], wall: float) -> None:
        self._oks = oks
        self._wall = wall

    def wall(self) -> float:
        return self._wall

    def result(self) -> List[bool]:
        return self._oks


class ParallelVerifyEngine:
    """Persistent worker pool for (pubkey, msg, sig) verification.

    verify() is bit-identical to the serial per-item loop: every lane
    runs the exact same ``pk.verify(msg, sig)`` the serial backend
    runs, only distributed; verdict order always matches input order
    regardless of chunk size or worker count (differential-tested in
    tests/test_parallel_verify.py)."""

    def __init__(
        self,
        workers: Optional[int] = None,
        tier: Optional[str] = None,
        chunk_target_s: Optional[float] = None,
        min_parallel: Optional[int] = None,
    ) -> None:
        env = os.environ
        if workers is None:
            w = env.get("GRAFT_VERIFY_WORKERS")
            workers = int(w) if w else min(
                os.cpu_count() or 1, _MAX_WORKERS_CAP
            )
        self.workers = max(1, workers)
        if tier is None:
            tier = env.get("GRAFT_VERIFY_TIER")
        if tier is None:
            tier = "thread" if _ed25519_releases_gil() else "process"
        if self.workers <= 1:
            tier = "serial"
        assert tier in ("thread", "process", "serial"), tier
        self.tier = tier
        if chunk_target_s is None:
            chunk_target_s = (
                float(env.get("GRAFT_VERIFY_CHUNK_TARGET_MS", "4.0"))
                / 1e3
            )
        self._chunk_target_s = chunk_target_s
        if min_parallel is None:
            mp = env.get("GRAFT_VERIFY_MIN_PARALLEL")
            min_parallel = int(mp) if mp else _DEFAULT_MIN_PARALLEL
        self.min_parallel = min_parallel
        # serial per-item cost EWMA; seeded by the init benchmark on
        # first pool use (the ~80us/sig OpenSSL figure from
        # crypto/batch.py's calibration is the prior)
        self._per_item_s = 80e-6
        self._calibrated = False
        self._pool = None
        self._lock = threading.Lock()
        # dispatch backpressure telemetry (obs/queues.py registry):
        # chunks submitted but not yet completed, worst case since
        # start, and total chunks dispatched
        self.inflight_chunks = 0
        self.inflight_hwm = 0
        self.chunks_dispatched = 0

    # --- pool / calibration ------------------------------------------

    def _calibrate(self) -> None:
        """Init-time benchmark (like crypto/batch.py's dispatch
        calibration): measure the serial per-item verify cost with a
        synthetic keypair so the FIRST real batch already gets a
        sensible chunk size. Pure-Python tiers are slow per verify, so
        the sample is small; the EWMA keeps refining from real chunk
        walls either way."""
        try:
            from .keys import Ed25519PrivKey

            priv = Ed25519PrivKey.from_seed(b"\x5a" * 32)
            pk = priv.pub_key()
            msg = b"parallel-verify-calibration"
            sig = priv.sign(msg)
            reps = 6 if _ed25519_releases_gil() else 2
            best = None
            for _ in range(reps):
                t0 = time.perf_counter()
                if not pk.verify(msg, sig):  # pragma: no cover
                    return
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            if best and best > 0:
                self._per_item_s = best
        except Exception:  # pragma: no cover - calibration is advisory
            pass
        self._calibrated = True

    def _ensure_pool(self):
        with self._lock:
            if self.tier == "serial":
                return None
            if self._pool is None:
                if not self._calibrated:
                    self._calibrate()
                try:
                    if self.tier == "thread":
                        from concurrent.futures import ThreadPoolExecutor

                        self._pool = ThreadPoolExecutor(
                            max_workers=self.workers,
                            thread_name_prefix="pverify",
                        )
                    else:
                        from concurrent.futures import (
                            ProcessPoolExecutor,
                        )

                        # fork-started children inherit the parent's
                        # enabled global tracer; their rings are
                        # unreadable (and COW-duplicated), so the
                        # traced path must stay no-op there
                        self._pool = ProcessPoolExecutor(
                            max_workers=self.workers,
                            initializer=_disable_worker_tracing,
                        )
                except (OSError, ImportError, RuntimeError):
                    # restricted container (no fork / thread limit):
                    # degrade to bit-identical serial verification
                    self.tier = "serial"
                    self._pool = None
            return self._pool

    def _chunk_submitted(self, n: int = 1) -> None:
        with self._lock:
            self.chunks_dispatched += n
            self.inflight_chunks += n
            if self.inflight_chunks > self.inflight_hwm:
                self.inflight_hwm = self.inflight_chunks

    def _chunk_done(self) -> None:
        with self._lock:
            if self.inflight_chunks > 0:
                self.inflight_chunks -= 1

    def queue_stats(self) -> dict:
        """Dispatch-queue backpressure (obs/queues.py registry).
        inflight > workers just means chunks are queued on the pool —
        normal under load — so the worker count is NOT reported as
        "maxsize" (the health route treats depth >= maxsize as a
        degraded full queue)."""
        with self._lock:
            return {
                "depth": self.inflight_chunks,
                "high_watermark": self.inflight_hwm,
                "enqueued": self.chunks_dispatched,
                "dropped": 0,
                "workers": self.workers,
            }

    def _observe_chunk(self, n: int, wall: float) -> None:
        if n <= 0 or wall <= 0:
            return
        with self._lock:
            self._per_item_s += _EWMA_ALPHA * (
                wall / n - self._per_item_s
            )

    def chunk_size(self, n: int) -> int:
        """Chunk lanes so each chunk amortizes submit/merge overhead
        (~chunk_target_s of serial work), while mid-size batches still
        spread over every worker."""
        with self._lock:
            per = max(self._per_item_s, 1e-7)
        c = max(_MIN_CHUNK, int(self._chunk_target_s / per))
        # a batch that fits in < workers time-sized chunks still fans
        # out: never leave workers idle to honor the time target
        c = min(c, max(_MIN_CHUNK, -(-n // self.workers)))
        return c

    def stats(self) -> dict:
        with self._lock:
            per = self._per_item_s
        return {
            "tier": self.tier,
            "workers": self.workers,
            "per_item_us": round(per * 1e6, 1),
            "min_parallel": self.min_parallel,
        }

    def close(self) -> None:
        with self._lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    # --- verification -------------------------------------------------

    def _serial(self, items) -> _ResolvedLanes:
        oks, wall = _verify_chunk(items, self.tier)
        self._observe_chunk(len(items), wall)
        return _ResolvedLanes(oks, wall)

    def verify_async(self, items: Sequence) -> "PendingLanes":
        """Enqueue the batch on the pool WITHOUT blocking on verdicts;
        the returned handle's ``result()`` blocks and merges. Small
        batches resolve eagerly (nothing to amortize)."""
        n = len(items)
        pool = self._ensure_pool() if n >= self.min_parallel else None
        if pool is None:
            return self._serial(items)
        if self.tier == "process":
            # chunks cross a pickle boundary: normalize to plain tuples
            items = [(pk, bytes(m), bytes(s)) for pk, m, s in items]
        chunk = self.chunk_size(n)
        tr = global_tracer()
        if tr.enabled:
            tr.instant(
                "crypto.batch.dispatch",
                tid="crypto",
                lanes=n,
                chunk=chunk,
                tier=self.tier,
                workers=self.workers,
            )
        futures = []
        try:
            for start in range(0, n, chunk):
                fut = pool.submit(
                    _verify_chunk, items[start : start + chunk],
                    self.tier,
                )
                self._chunk_submitted()
                futures.append((start, fut))
        except RuntimeError:
            # pool shut down underneath us (interpreter teardown):
            # fall back serially for the lanes not yet submitted —
            # verdicts must never be lost
            done = futures[-1][0] + chunk if futures else 0
            tail = self._serial(items[done:])
            pending = PendingLanes(futures, self, done)
            return _ResolvedLanes(
                pending.result() + tail.result(), tail.wall() or 0.0
            )
        return PendingLanes(futures, self, n)

    def verify(self, items: Sequence) -> List[bool]:
        """Order-stable parallel verify; blocking."""
        return self.verify_async(items).result()


# --- process-wide default engine ----------------------------------------

_ENGINE: Optional[ParallelVerifyEngine] = None
_ENGINE_LOCK = threading.Lock()


def engine() -> ParallelVerifyEngine:
    """The shared engine every host-routed ticket of the verify
    scheduler rides (the cpu-parallel backend, and the tpu and mesh
    backends' host-routed lanes). Created lazily on first use."""
    global _ENGINE
    with _ENGINE_LOCK:
        if _ENGINE is None:
            _ENGINE = ParallelVerifyEngine()
        return _ENGINE


def dispatch_stats_if_running():
    """The shared engine's dispatch-queue telemetry, or None when no
    engine was ever built — the obs registry entry must never CREATE
    the engine (pool spin-up) just to report an idle plane."""
    with _ENGINE_LOCK:
        e = _ENGINE
    return None if e is None else e.queue_stats()


def set_engine(e: Optional[ParallelVerifyEngine]) -> None:
    """Swap the process-wide engine (tests / operator reconfig); the
    old pool keeps draining already-submitted chunks."""
    global _ENGINE
    with _ENGINE_LOCK:
        _ENGINE = e
