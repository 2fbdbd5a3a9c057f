"""Async coalescing signature-verification queue (the consensus-round
hot-path batcher).

The reference verifies live votes one at a time on the CPU
(types/vote.go:237 via consensus/state.go:2175 addVote) — fine for a
CPU whose single verify costs ~60us. A TPU dispatch has fixed latency,
so the win only appears when a round's vote WAVE (one vote per
validator, arriving in a burst) is verified as one lane batch. This
queue is that seam: requests arriving within ``window_s`` (or until
``max_pending``) are verified as ONE live-class ticket of the verify
scheduler (crypto/scheduler.py), each submitter getting its own
future. Verified signatures land in the shared SignatureCache
(reference types/signature_cache.go) so the consensus state machine's
inline re-verify is a cache hit, preserving its single-writer design.

BASELINE.json north star: "a host-side async queue coalesces
signatures across heights/blocks"; SURVEY.md §7 stage 1.
"""

from __future__ import annotations

import asyncio
from typing import List, Optional, Tuple

from . import scheduler as crypto_sched
from .scheduler import PRIORITY_LIVE, _host_verify_one
from ..utils.log import get_logger

_log = get_logger("coalesce")

# window long enough to collect a gossip burst, short enough to add no
# visible latency to a round (consensus timeouts are 100ms+)
DEFAULT_WINDOW_S = 0.002
DEFAULT_MAX_PENDING = 8192


class CoalescingVerifier:
    """Window-batched async verifier with per-request futures."""

    def __init__(
        self,
        cache=None,
        window_s: float = DEFAULT_WINDOW_S,
        max_pending: int = DEFAULT_MAX_PENDING,
        priority: int = PRIORITY_LIVE,
    ):
        self.cache = cache
        self.window_s = window_s
        self.max_pending = max_pending
        # verify-scheduler class for dispatched windows: the consensus
        # vote wave IS the live round, so LIVE by default
        self.priority = priority
        self._pending: List[Tuple] = []
        self._timer: Optional[asyncio.Task] = None
        self._inflight: set = set()
        # stats (asserted by tests; exported by node metrics)
        self.submitted = 0
        self.dispatches = 0
        self.cache_hits = 0

    def submit(self, pub_key, sign_bytes: bytes, sig: bytes) -> asyncio.Future:
        """Queue one (pubkey, sign_bytes, sig) for verification.

        Returns a future resolving to the bool verdict. Must be called
        on the event loop thread.
        """
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self.submitted += 1
        if self.cache is not None and self.cache.contains(
            sign_bytes, sig, pub_key.key_bytes
        ):
            self.cache_hits += 1
            fut.set_result(True)
            return fut
        self._pending.append((pub_key, sign_bytes, sig, fut))
        if len(self._pending) >= self.max_pending:
            self._flush_now()
        elif self._timer is None:
            self._timer = loop.create_task(self._window())
        return fut

    def _flush_now(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        t = asyncio.ensure_future(self._dispatch())
        self._inflight.add(t)
        t.add_done_callback(self._inflight.discard)

    def flush(self) -> None:
        """Dispatch whatever is pending right now (no-op when empty).

        Callers that know the natural batch boundary — the consensus
        receive loop draining its inbox, a reactor finishing a read
        burst — flush explicitly instead of waiting out the window
        timer: on a busy loop the timer callback can starve for tens
        of milliseconds behind queued work, turning the micro-batch
        window into real quorum latency. The timer stays as the
        backstop for callers without such a boundary."""
        if self._pending:
            self._flush_now()

    async def _window(self) -> None:
        try:
            await asyncio.sleep(self.window_s)
        except asyncio.CancelledError:
            return
        self._timer = None
        await self._dispatch()

    async def _dispatch(self) -> None:
        items, self._pending = self._pending, []
        if not items:
            return
        self.dispatches += 1
        try:
            # one LIVE-class ticket through the unified scheduler; the
            # blocking resolve rides a worker thread so the loop stays
            # free (the scheduler's dispatch may device-compile or
            # grind host crypto — both release the GIL)
            ticket = crypto_sched.scheduler().submit(
                [(pk, sb, sig) for pk, sb, sig, _fut in items],
                priority=self.priority,
                label="vote-wave",
            )
            _, oks = await asyncio.to_thread(ticket.result)
        except asyncio.CancelledError:
            raise  # engine stop cancels the dispatch task
        except Exception as e:
            # A transient backend/device failure must not discard a
            # whole wave of valid votes (the reactor already announced
            # has_vote for them, so they would never be re-gossiped and
            # round liveness degrades). Resolve each lane by per-item
            # host verification instead — correctness is identical, the
            # batch was only ever an optimization.
            _log.error(
                "batch verify dispatch failed; falling back to per-item "
                "host verification",
                n=len(items),
                err=repr(e),
            )

            def _host_verify_all():
                return [
                    _host_verify_one((pk, sb, sig))
                    for pk, sb, sig, _fut in items
                ]

            oks = await asyncio.to_thread(_host_verify_all)
        for (pk, sb, sig, fut), ok in zip(items, oks):
            if ok and self.cache is not None:
                self.cache.add(sb, sig, pk.key_bytes)
            if not fut.done():
                fut.set_result(bool(ok))

    async def drain(self) -> None:
        """Flush pending work and wait for in-flight dispatches
        (tests/shutdown)."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        await self._dispatch()
        while self._inflight:
            await asyncio.gather(*list(self._inflight), return_exceptions=True)
