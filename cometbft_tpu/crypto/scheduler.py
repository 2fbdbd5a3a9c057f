"""Unified verify scheduler: ONE dispatch queue for every signature
verification consumer (docs/PERF.md "Unified verify scheduler").

Every consumer (types/validation's commit seams, the consensus vote
coalescer crypto/coalesce, the light serving plane, blocksync,
statesync, evidence) submits its lanes here (``(pubkey, msg, sig)``
tuples, or a crypto/lanes.LaneBatch: the ed25519 lanes of a window by
columns, which go through to the device arrays with no Python step a
lane) and gets a ticket; nothing else reaches the kernel or the host
pool, so a live round's precommit wave never queues behind a
500-block catch-up window. A ticket's life: queue (by class) -> ``_plan`` (lane
split by curve + the one routing decision, ``crypto/batch.decide``)
-> ``ops/ed25519.verify_batch_async`` (pack, put, the jitted program,
one device or lane-sharded over a mesh) and a watcher thread, or
calibrated host chunks on the parallel plane -> verdicts merged back
in submission order.

- **Priority classes**: live round (0) > light session (1) >
  catch-up/evidence (2). Dispatch granularity is one calibrated chunk
  (~4 ms of host work, crypto/parallel_verify.chunk_size), so a live
  batch arriving mid-storm preempts at the next chunk boundary — a
  bounded wait of roughly workers x chunk-wall, never the storm's
  full residue.
- **Starvation guard**: a queued ticket older than ``promote_after_s``
  is served ahead of higher classes once every ``promote_every``
  picks — catch-up keeps a bounded 1/promote_every share of dispatch
  slots under ANY sustained live load (tests/test_verify_scheduler).
- **Per-backend lanes + calibrated routing**: WHERE a ticket's
  ed25519 lanes verify is decided in one place, crypto/batch.decide:
  the configured backend name, the batch floor, the measured
  host-vs-device crossover EWMA (crypto/batch.calibration) with its
  explore/recovery schedule. The scheduler only decides WHEN. Device
  dispatches ride the async XLA seam; a readiness watcher feeds the
  calibration with the true dispatch wall and resolves the ticket.
  The ``mesh`` backend shards lanes over every local device and
  degrades to host chunks when no mesh materializes or the dispatch
  fails (``ticket.backend`` ``mesh-degraded``, the ``degraded``
  counter).

Verdicts are serial-equivalent BY CONSTRUCTION: every lane runs
``pk.verify`` or the kernel's same math, merged back in submission
order (differential-tested against crypto/batch.CpuBatchVerifier in
tests/test_verify_scheduler.py).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..trace import global_tracer, ticket_scope
from ..utils.log import get_logger
from . import batch as crypto_batch
from .keys import Ed25519PubKey
from .lanes import LaneBatch

_log = get_logger("crypto.sched")

# Priority classes, lower value = served first.
PRIORITY_LIVE = 0
PRIORITY_LIGHT = 1
PRIORITY_CATCHUP = 2

CLASS_NAMES = ("live", "light", "catchup")

# Starvation guard defaults: a ticket queued longer than this is
# "aged"; one aged chunk is served per PROMOTE_EVERY picks while any
# aged ticket exists, so lower classes keep a bounded share of
# dispatch slots under sustained higher-class load.
DEFAULT_PROMOTE_AFTER_S = 0.25
DEFAULT_PROMOTE_EVERY = 4

# Process-unique ticket ids: every span a ticket leaves on the process
# tracer carries ``ticket=<id>`` (docs/TRACE.md "One ticket, one
# timeline"); ``next()`` of a count is atomic under the GIL.
_TICKET_IDS = itertools.count(1)

# Span rows (``tid``), one a thread's role: the ticket's root and its
# queue wait, the dispatcher thread, a device watcher thread, and
# whichever thread resolves a host-routed ticket.
_TID_TICKET = "crypto.sched"
_TID_DISPATCHER = "crypto.sched.dispatcher"
_TID_WATCHER = "crypto.sched.watcher"
_TID_HOST = "crypto.sched.host"


def _clamp_priority(priority) -> int:
    try:
        p = int(priority)
    except (TypeError, ValueError):
        return PRIORITY_CATCHUP
    return min(max(p, PRIORITY_LIVE), PRIORITY_CATCHUP)


class VerifyTicket:
    """One submitted batch: ``result()`` blocks for the merged
    verdicts and returns ``(all_ok, oks)``, ``oks`` in submission
    order (what crypto/batch.CpuBatchVerifier.verify returns): a
    list of bool for a ticket of tuples, a bool array for a columnar
    one (``items`` a LaneBatch)."""

    __slots__ = (
        "id", "items", "columnar", "priority", "label", "t_submit",
        "t_submit_ns", "t_done", "oks", "backend", "depth_ahead",
        "_chunks", "_units_left", "_event", "_routed",
    )

    def __init__(self, items, priority: int, label: str) -> None:
        self.id = next(_TICKET_IDS)
        self.items = items
        self.columnar = isinstance(items, LaneBatch)
        self.priority = priority
        self.label = label
        self.t_submit = time.perf_counter()
        # the same instant on the tracer's clock: where the ticket's
        # root span and its queue wait start
        self.t_submit_ns = time.monotonic_ns()
        self.depth_ahead = 0  # lanes queued ahead of it at submit
        self.t_done: Optional[float] = None
        # a bool array while the ticket is worked on (every write-back
        # is one assignment); _finish makes a tuple ticket's a list
        self.oks = np.zeros(len(items), bool)
        self.backend: Optional[str] = None
        self._chunks: deque = deque()
        self._units_left = 0
        self._event = threading.Event()
        self._routed = False

    def result(self, timeout: Optional[float] = None) -> Tuple[bool, List[bool]]:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"verify ticket ({len(self.items)} lanes, "
                f"class={CLASS_NAMES[self.priority]}) not resolved "
                f"within {timeout}s"
            )
        oks = self.oks
        return len(oks) > 0 and bool(np.all(oks)), oks

    def done(self) -> bool:
        return self._event.is_set()

    def wall(self) -> Optional[float]:
        """Submit→resolve wall (queue wait INCLUDED — the latency the
        priority classes exist to bound), or None while pending."""
        done = self.t_done
        return None if done is None else done - self.t_submit


class VerifyScheduler:
    """Single dispatch queue with priority classes and per-backend
    lanes. Thread-safe; one daemon dispatcher thread started lazily on
    first submit."""

    def __init__(
        self,
        promote_after_s: float = DEFAULT_PROMOTE_AFTER_S,
        promote_every: int = DEFAULT_PROMOTE_EVERY,
    ) -> None:
        self.promote_after_s = promote_after_s
        self.promote_every = max(1, promote_every)
        self._cv = threading.Condition()
        self._queues: Tuple[deque, ...] = (deque(), deque(), deque())
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._promo_credit = 0
        # host-pool backpressure: chunks in flight on the shared pool,
        # bounded to the worker count so a late-arriving live ticket
        # waits at most one chunk-wall per worker
        self._inflight = 0
        self._max_slots: Optional[int] = None
        # stats (obs registry + tests + bench)
        self.enqueued_lanes = 0
        self.done_lanes = 0
        self.enqueued_by_class = [0, 0, 0]
        self.done_by_class = [0, 0, 0]
        self.depth_hwm = 0
        self.promoted = 0
        self.device_dispatches = 0
        self.host_chunks = 0
        self.degraded = 0
        self.tickets = 0
        self.columnar_tickets = 0

    # --- submission ----------------------------------------------------

    def submit(
        self,
        items: Sequence,
        priority: int = PRIORITY_CATCHUP,
        label: str = "",
    ) -> VerifyTicket:
        """Queue lanes for verification under a priority class;
        returns immediately with a VerifyTicket. ``items``: a sequence
        of (pubkey, msg, sig), any curve, copied; or a
        crypto/lanes.LaneBatch (ed25519 lanes by columns, none
        refused, as types/validation builds them), kept as it is:
        its ticket's verdicts are a bool array."""
        priority = _clamp_priority(priority)
        ticket = VerifyTicket(
            items if isinstance(items, LaneBatch) else list(items),
            priority, label,
        )
        if not len(ticket.items):
            # empty batch resolves to (False, []) like CpuBatchVerifier
            ticket.oks = []
            ticket.t_done = ticket.t_submit
            ticket._event.set()
            return ticket
        with self._cv:
            if self._closed:
                raise RuntimeError("verify scheduler closed")
            self.tickets += 1
            self.columnar_tickets += ticket.columnar
            n = len(ticket.items)
            self.enqueued_lanes += n
            self.enqueued_by_class[priority] += n
            self._queues[priority].append(ticket)
            depth = self.enqueued_lanes - self.done_lanes
            ticket.depth_ahead = depth - n
            if depth > self.depth_hwm:
                self.depth_hwm = depth
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop,
                    name="verify-sched",
                    daemon=True,
                )
                self._thread.start()
            self._cv.notify_all()
        return ticket

    # --- dispatcher ----------------------------------------------------

    def _slots(self) -> int:
        if self._max_slots is None:
            from .parallel_verify import engine

            self._max_slots = max(1, engine().workers)
        return self._max_slots

    def _pick_locked(self) -> Optional[VerifyTicket]:
        """Highest-priority non-empty class, with the bounded aging
        promotion (starvation guard). Caller holds the lock."""
        best_cls = None
        for cls in (PRIORITY_LIVE, PRIORITY_LIGHT, PRIORITY_CATCHUP):
            if self._queues[cls]:
                best_cls = cls
                break
        if best_cls is None:
            return None
        now = time.perf_counter()
        aged = None
        for cls in range(best_cls + 1, len(self._queues)):
            q = self._queues[cls]
            if q and now - q[0].t_submit > self.promote_after_s:
                if aged is None or q[0].t_submit < aged.t_submit:
                    aged = q[0]
        if aged is not None:
            self._promo_credit += 1
            if self._promo_credit >= self.promote_every:
                self._promo_credit = 0
                self.promoted += 1
                return aged
        return self._queues[best_cls][0]

    def _loop(self) -> None:
        while True:
            with self._cv:
                ticket = None
                while True:
                    if self._inflight < self._slots():
                        ticket = self._pick_locked()
                    if ticket is not None or self._closed:
                        break
                    # bounded wait: aging promotions must be
                    # re-evaluated even with no new submissions
                    self._cv.wait(0.05)
                if ticket is None and self._closed:
                    return
                if ticket is None:
                    continue
                if ticket._routed:
                    chunk = ticket._chunks.popleft()
                    if not ticket._chunks:
                        self._queues[ticket.priority].remove(ticket)
                else:
                    chunk = None
                    self._queues[ticket.priority].remove(ticket)
            try:
                if chunk is None:
                    # nobody worked on it since submit: a wait, so
                    # measured after the fact and on the ring alone
                    global_tracer().complete(
                        "crypto.sched.queue_wait",
                        ticket.t_submit_ns,
                        time.monotonic_ns() - ticket.t_submit_ns,
                        tid=_TID_TICKET,
                        ticket=ticket.id,
                        cls=CLASS_NAMES[ticket.priority],
                        depth=ticket.depth_ahead,
                    )
                    self._route(ticket)
                else:
                    self._run_chunk(ticket, chunk)
            except Exception as e:  # pragma: no cover - last resort
                # verdicts must never be lost: resolve the affected
                # lanes by per-item host verification
                _log.error(
                    "verify dispatch failed; per-item host fallback",
                    err=repr(e),
                    lanes=len(ticket.items),
                )
                self._fallback_serial(ticket, chunk)

    # --- routing -------------------------------------------------------

    def _route(self, ticket: VerifyTicket) -> None:
        """First pop: split lanes by curve, take the routing decision
        (crypto/batch.decide), dispatch the device part async, queue
        the host part as calibrated chunks."""
        # the span ends before the device dispatch begins:
        # ops.ed25519.pack / .enqueue are stages of their own
        with global_tracer().annotated_span(
            "crypto.sched.route", tid=_TID_DISPATCHER,
            ticket=ticket.id, lanes=len(ticket.items),
            form="columns" if ticket.columnar else "tuples",
        ) as sp:
            plan = self._plan(ticket)
            sp.set(path=plan[0])
        path, ed_idx, ed_items = plan
        backend = ticket.backend
        if path == "device" and len(ed_idx):
            if self._dispatch_device(ticket, ed_idx, ed_items, backend):
                return
            # device dispatch failed: re-route the lanes to host
            ticket.backend = f"{backend}-degraded"
            self.degraded += 1
        self._queue_host_chunks(ticket, ed_idx)

    def _plan(self, ticket: VerifyTicket) -> tuple:
        """Split the lanes by curve and ask the routing decision for
        the ed25519 ones: (path, ed_idx, ed_items) with path
        ``device`` | ``host``, ``ed_idx`` their positions in the
        ticket and ``ed_items`` what ops/ed25519.verify_batch_async
        takes. A columnar ticket (``items`` a LaneBatch) is ed25519 by
        construction: every lane, the batch itself, no pass over the
        lanes. A ticket of ``(pubkey, msg, sig)`` tuples is split a
        lane at a time into ``(msg, key_bytes, sig)`` tuples; its
        other curves verify inline, here."""
        items = ticket.items
        if ticket.columnar:
            path, ticket.backend, degraded = crypto_batch.decide(len(items))
            if degraded:
                self.degraded += 1
            ticket._routed = True
            return path, range(len(items)), items
        ed_idx: List[int] = []
        ed_items = []
        other_idx: List[int] = []
        for i, (pk, msg, sig) in enumerate(items):
            if isinstance(pk, Ed25519PubKey):
                ed_idx.append(i)
                ed_items.append((msg, pk.key_bytes, sig))
            else:
                other_idx.append(i)
        path, ticket.backend, degraded = crypto_batch.decide(len(ed_items))
        if degraded:
            self.degraded += 1
        # non-ed lanes: verified inline at route time (rare curves)
        for i in other_idx:
            pk, msg, sig = items[i]
            ticket.oks[i] = pk.verify(msg, sig)
        ticket._routed = True
        return path, ed_idx, ed_items

    def _dispatch_device(
        self, ticket: VerifyTicket, ed_idx, ed_items, backend: str
    ) -> bool:
        """Async device dispatch for the ed25519 lanes; a daemon
        watcher feeds the calibration EWMA from true readiness
        (``wait()``, not ``result()``: a caller that overlaps host work
        before resolving must not inflate the observed wall) and
        resolves the ticket. Returns False when the dispatch itself
        fails."""
        try:
            from ..ops import ed25519 as _ed

            t0 = time.perf_counter()
            # ops.ed25519.pack / .enqueue learn the ticket from the
            # thread, not from an argument: the call keeps its shape
            with ticket_scope(ticket.id, _TID_DISPATCHER):
                handle = _ed.verify_batch_async(ed_items)
        except Exception as e:
            _log.error(
                "device dispatch failed; host chunks",
                backend=backend,
                err=repr(e),
                lanes=len(ed_items),
            )
            return False
        self.device_dispatches += 1
        ticket._units_left += 1
        n_ed = len(ed_items)
        cal = crypto_batch.calibration

        def _watch():
            tr = global_tracer()
            sp = None
            try:
                with tr.annotated_span(
                    "crypto.sched.device_wait", tid=_TID_WATCHER,
                    ticket=ticket.id, lanes=n_ed,
                ):
                    handle.wait()
                    wall = time.perf_counter() - t0
                sp = tr.annotated_span(
                    "crypto.sched.resolve", tid=_TID_WATCHER,
                    ticket=ticket.id, lanes=n_ed,
                )
                cal.observe_device(n_ed, wall)
                # ops.ed25519.fetch learns the ticket from the thread
                with ticket_scope(ticket.id, _TID_WATCHER):
                    verdicts = handle.result()
            except Exception as e:
                _log.error(
                    "device resolve failed; per-item host fallback",
                    err=repr(e),
                    lanes=n_ed,
                )
                verdicts = [
                    _host_verify_one(ticket.items[i]) for i in ed_idx
                ]
            ticket.oks[_at(ed_idx)] = verdicts
            self._unit_done(ticket, n_ed, sp)

        threading.Thread(
            target=_watch, name="verify-sched-dev", daemon=True
        ).start()
        return True

    def _queue_host_chunks(self, ticket: VerifyTicket, ed_idx) -> None:
        """Chunk the host-routed ed25519 lanes (calibrated ~4 ms of
        serial work each — the preemption granularity) and requeue the
        ticket at the FRONT of its class so its chunks drain before
        later same-class arrivals."""
        if not len(ed_idx):
            if ticket._units_left == 0:
                self._finish(ticket, 0)
            return
        from .parallel_verify import engine

        eng = engine()
        chunk = eng.chunk_size(len(ed_idx))
        chunks = [
            ed_idx[s : s + chunk] for s in range(0, len(ed_idx), chunk)
        ]
        with self._cv:
            ticket._chunks.extend(chunks)
            ticket._units_left += len(chunks)
            self._queues[ticket.priority].appendleft(ticket)
            self._cv.notify_all()

    # --- host execution ------------------------------------------------

    def _run_chunk(self, ticket: VerifyTicket, idx_chunk) -> None:
        """One host chunk: on the shared pool when it pays (slot-
        bounded so priorities hold at chunk granularity), inline on
        the dispatcher thread otherwise (serial tier / tiny work)."""
        from .parallel_verify import _verify_chunk, engine

        eng = engine()
        chunk_items = [ticket.items[i] for i in idx_chunk]
        self.host_chunks += 1
        pool = None
        if ticket.backend != "cpu" and len(ticket.items) >= eng.min_parallel:
            pool = eng._ensure_pool()
        if pool is None:
            oks, wall = _verify_chunk(chunk_items, eng.tier)
            self._chunk_resolved(ticket, idx_chunk, oks, wall, eng)
            return
        if eng.tier == "process":
            chunk_items = [
                (pk, bytes(m), bytes(s)) for pk, m, s in chunk_items
            ]
        with self._cv:
            self._inflight += 1
        try:
            fut = pool.submit(_verify_chunk, chunk_items, eng.tier)
        except RuntimeError:
            # pool shut down underneath us (teardown): inline
            with self._cv:
                self._inflight -= 1
            oks, wall = _verify_chunk(chunk_items, eng.tier)
            self._chunk_resolved(ticket, idx_chunk, oks, wall, eng)
            return
        eng._chunk_submitted()

        def _done(f):
            eng._chunk_done()
            with self._cv:
                self._inflight -= 1
                self._cv.notify_all()
            try:
                oks, wall = f.result()
            except Exception:  # pragma: no cover - worker died
                oks = [
                    _host_verify_one(ticket.items[i]) for i in idx_chunk
                ]
                wall = 0.0
            self._chunk_resolved(ticket, idx_chunk, oks, wall, eng)

        fut.add_done_callback(_done)

    def _chunk_resolved(self, ticket, idx_chunk, oks, wall, eng) -> None:
        ticket.oks[_at(idx_chunk)] = oks
        n = len(idx_chunk)
        if wall:
            eng._observe_chunk(n, wall)
            if ticket.backend == "tpu":
                # host-vs-device routing EWMA: fed only on the backend
                # whose routing consults it (the cpu backends never
                # calibrate)
                crypto_batch.calibration.observe_host(n, wall)
        self._unit_done(ticket, n)

    def _fallback_serial(self, ticket, idx_chunk) -> None:
        idx = idx_chunk if idx_chunk is not None else range(len(ticket.items))
        for i in idx:
            ticket.oks[i] = _host_verify_one(ticket.items[i])
        if idx_chunk is None:
            # routing never completed: the whole ticket is resolved
            ticket._routed = True
            self._finish(ticket, len(ticket.items))
        else:
            self._unit_done(ticket, len(idx_chunk))

    # --- completion ----------------------------------------------------

    def _unit_done(self, ticket: VerifyTicket, lanes: int, sp=None) -> None:
        """``sp``: the watcher's open ``crypto.sched.resolve`` span,
        handed on to ``_finish`` (a device dispatch is its ticket's
        one unit, so always the last)."""
        with self._cv:
            ticket._units_left -= 1
            last = ticket._units_left <= 0 and not ticket._chunks
        if last:
            self._finish(ticket, len(ticket.items), sp)

    def _finish(self, ticket: VerifyTicket, lanes: int, sp=None) -> None:
        tr = global_tracer()
        if sp is None:
            # host-routed ticket: the verdicts were written back chunk
            # by chunk, what is left is this
            sp = tr.annotated_span(
                "crypto.sched.resolve", tid=_TID_HOST,
                ticket=ticket.id, lanes=len(ticket.items),
            )
        if not ticket.columnar and isinstance(ticket.oks, np.ndarray):
            ticket.oks = ticket.oks.tolist()
        ticket.t_done = time.perf_counter()
        with self._cv:
            n = len(ticket.items)
            self.done_lanes += n
            self.done_by_class[ticket.priority] += n
            self._cv.notify_all()
        sp.end()
        # the ticket's root: submit (stamped then, on this clock) to
        # here, so every stage span above lies inside it
        tr.complete(
            "crypto.sched.dispatch",
            ticket.t_submit_ns,
            time.monotonic_ns() - ticket.t_submit_ns,
            tid=_TID_TICKET,
            ticket=ticket.id,
            cls=CLASS_NAMES[ticket.priority],
            backend=ticket.backend or "?",
            lanes=len(ticket.items),
        )
        ticket._event.set()

    # --- observability / lifecycle -------------------------------------

    def queue_stats(self) -> dict:
        """Backpressure snapshot (obs/queues.py registry): pending
        lane depth overall + per class. Queued-but-unrouted tickets
        count every lane; routed tickets count their unfinished
        chunks' share. No ``maxsize`` — the queue is unbounded by
        design, depth is load, not overload."""
        with self._cv:
            depth = self.enqueued_lanes - self.done_lanes
            per = {}
            for cls, name in enumerate(CLASS_NAMES):
                per[f"{name}_depth"] = (
                    self.enqueued_by_class[cls] - self.done_by_class[cls]
                )
            out = {
                "depth": max(depth, 0),
                "high_watermark": self.depth_hwm,
                "enqueued": self.enqueued_lanes,
                "dropped": 0,
                "inflight_chunks": self._inflight,
                "promoted": self.promoted,
                "device_dispatches": self.device_dispatches,
                "host_chunks": self.host_chunks,
                "degraded": self.degraded,
            }
            out.update(per)
            return out

    def stats(self) -> dict:
        with self._cv:
            return {
                "tickets": self.tickets,
                "columnar_tickets": self.columnar_tickets,
                "lanes": self.enqueued_lanes,
                "by_class": {
                    name: self.enqueued_by_class[cls]
                    for cls, name in enumerate(CLASS_NAMES)
                },
                "promoted": self.promoted,
                "device_dispatches": self.device_dispatches,
                "host_chunks": self.host_chunks,
                "degraded": self.degraded,
            }

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every submitted lane resolved (tests/bench)."""
        deadline = time.perf_counter() + timeout
        with self._cv:
            while self.done_lanes < self.enqueued_lanes:
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.1))
        return True

    def close(self) -> None:
        """Stop the dispatcher after the queue drains (shutdown)."""
        self.drain(timeout=5.0)
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)


def _at(idx):
    """Index of a ticket's verdicts for the lanes ``idx``: a run of
    them (a columnar ticket's, or a chunk of one) as a slice."""
    return slice(idx.start, idx.stop) if isinstance(idx, range) else idx


def _host_verify_one(item) -> bool:
    """Per-item host verification — the never-raises fallback lane."""
    pk, msg, sig = item
    try:
        return bool(pk.verify(msg, sig))
    except Exception:
        return False


# --- process-wide default scheduler --------------------------------------

_SCHED: Optional[VerifyScheduler] = None
_SCHED_LOCK = threading.Lock()


def scheduler() -> VerifyScheduler:
    """The shared scheduler every verify consumer submits through
    (types/validation, the consensus vote coalescer, light serving,
    blocksync, statesync, evidence). Created lazily on first use."""
    global _SCHED
    with _SCHED_LOCK:
        if _SCHED is None:
            _SCHED = VerifyScheduler()
        return _SCHED


def set_scheduler(s: Optional[VerifyScheduler]) -> None:
    """Swap the process-wide scheduler (tests / operator reconfig)."""
    global _SCHED
    with _SCHED_LOCK:
        old, _SCHED = _SCHED, s
    if old is not None and old is not s:
        old.close()


def sched_stats_if_running() -> Optional[dict]:
    """Queue-depth gauges for the obs registry, or None when no
    scheduler was ever built — the registry entry must never CREATE
    the scheduler (dispatcher spin-up) just to report an idle plane."""
    with _SCHED_LOCK:
        s = _SCHED
    return None if s is None else s.queue_stats()
