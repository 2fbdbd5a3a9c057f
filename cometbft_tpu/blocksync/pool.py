"""Block pool: pipelined block download from peers (reference blocksync/pool.go).

Requesters fetch a sliding window of heights concurrently; blocks are
handed to the verify loop strictly in order. Peer quality feedback
(fork feature: banned peers + adaptive peer sorting, reference
blocksync/pool.go:79-84,504-522):

- the pool counts every response's wire bytes and keeps a receive rate
  a peer, over the time the peer had requests pending and the loop was
  running; a new request goes to the peer that by its rate and its
  queue would answer it soonest (``_pick_peer``);
- a peer whose queue the pool has kept full for ``RATE_EVIDENCE_S``
  and that delivered under ``MIN_RECV_RATE`` meanwhile is banned
  (``_check_rates``), as is one that times out, fails or serves a bad
  block;
- a ban, for whatever reason, takes back every request in flight at
  the peer and every block it has buffered, and re-routes them at once
  (reference RemovePeerAndRedoAllPeerRequests).
"""

from __future__ import annotations

import asyncio
import random
import time
import traceback
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..trace import NOOP as TRACE_NOOP

REQUEST_TIMEOUT_S = 10.0
# the reference's minRecvRate (blocksync/pool.go:33-44): a peer that has
# requests pending and delivers under 128 KB/s is dropped
MIN_RECV_RATE = 131_072
RATE_EVIDENCE_S = 1.0
MONITOR_TICK_S = 0.1
MAX_PENDING = 64
BAN_DURATION_S = 60.0


def _now() -> float:
    """Monotonic clock, module-level so tests can fake ban expiry
    without touching the event loop's time.monotonic."""
    return time.monotonic()


class PeerError(Exception):
    def __init__(self, peer_id: str, msg: str):
        super().__init__(msg)
        self.peer_id = peer_id


@dataclass
class PoolPeer:
    peer_id: str
    client: object  # BlockSyncPeerClient: async request_block(h)
    base: int = 0
    height: int = 0
    # height -> ``mark`` when the request was sent, oldest request first
    inflight: Dict[int, Optional[Tuple[float, int]]] = field(
        default_factory=dict
    )
    requests: int = 0
    blocks: int = 0
    bytes: int = 0
    timeouts: int = 0
    redone: int = 0  # requests taken back by a ban or a removal
    biggest: int = 0  # the largest response yet, bytes
    # (when, ``bytes`` after it) of the first and of the latest response
    # since requests have been pending without a break; None before
    first: Optional[Tuple[float, int]] = None
    mark: Optional[Tuple[float, int]] = None
    # seconds with requests pending, on the pool's running clock
    busy_s: float = 0.0
    busy_since: Optional[float] = None
    banned: Optional[Tuple[str, float]] = None  # (reason, s since pool start)

    @property
    def pending(self) -> int:
        return len(self.inflight)

    def serves(self, height: int) -> bool:
        return self.base <= height <= self.height

    def rate_bps(self, clock: float) -> Optional[float]:
        """Bytes received over the time requests were pending; None
        for a peer that has not had one pending yet."""
        busy = self.busy_s
        if self.busy_since is not None:
            busy += clock - self.busy_since
        return self.bytes / busy if busy > 0 else None


class BlockPool:
    """Downloads [start_height ..] keeping ``self.max_pending`` in
    flight (defaults to MAX_PENDING; the reactor raises it to cover
    its verify-window lookahead — see start_requesters)."""

    def __init__(self, start_height: int):
        self.start_height = start_height
        self.height = start_height  # next height to hand to verify loop
        self.max_pending = MAX_PENDING  # see start_requesters note
        self.peers: Dict[str, PoolPeer] = {}
        # bans live on the POOL, not the PoolPeer: a banned peer that
        # disconnects and re-dials (peer churn) must still be banned,
        # or a byzantine feeder can launder its ban with a reconnect
        self.banned_until: Dict[str, float] = {}
        self.blocks: Dict[int, Tuple[object, str]] = {}  # h -> (block, peer)
        # backpressure telemetry (obs/queues.py registry): worst
        # buffered-window size since start — the pool's pending window
        # is the blocksync plane's bounded queue
        self.blocks_hwm = 0
        # soft per-height exclusions (e.g. "peer lacks the extended
        # commit for h"): skipped when alternatives exist, ignored
        # otherwise — never a liveness risk, unlike a ban
        self.excluded: Dict[int, set] = {}
        self.tracer = TRACE_NOOP  # the reactor hands its own on
        self._tasks: Dict[int, asyncio.Task] = {}
        self._monitor_task: Optional[asyncio.Task] = None
        self._new_block = asyncio.Event()
        self._peers_changed = asyncio.Event()
        self._stopped = False
        self.start_time = _now()
        # the running clock: _now() less the time the loop was held
        # (a synchronous apply), which is nobody's receive time
        self._held_s = 0.0
        self._last_tick = self.start_time
        # the loop waits for one height while later ones sit buffered
        self.head_waits = 0
        self.head_wait_s = 0.0
        self._head_wait_since: Optional[float] = None

    # --- peers --------------------------------------------------------

    def set_peer_range(self, peer_id: str, client, base: int, height: int):
        p = self.peers.get(peer_id)
        if p is None:
            self.peers[peer_id] = PoolPeer(
                peer_id, client, base=base, height=height
            )
        else:
            p.base, p.height = base, height
        self._peers_changed.set()
        # a taller peer may unlock new heights (peers can appear/grow
        # AFTER the pool started in the networked path)
        self.start_requesters()

    def remove_peer(self, peer_id: str) -> None:
        self._take_back(peer_id)
        self.peers.pop(peer_id, None)

    def ban_peer(
        self,
        peer_id: str,
        reason: str = "failed",
        rate_bps: Optional[float] = None,
        since: Optional[float] = None,
    ) -> None:
        """Ban, and re-route what the peer holds. ``since`` is where
        the evidence began (the span's start), now if not given."""
        now = _now()
        self.banned_until[peer_id] = now + BAN_DURATION_S
        peer = self.peers.get(peer_id)
        pending = peer.pending if peer is not None else 0
        if peer is not None:
            peer.banned = (reason, now - self.start_time)
        redone = self._take_back(peer_id)
        since = now if since is None else since
        self.tracer.complete(
            "blocksync.pool.ban", int(since * 1e9), int((now - since) * 1e9),
            tid="blocksync", peer=peer_id, reason=reason,
            rate_bps=rate_bps, redone=redone, pending=pending,
        )

    def _take_back(self, peer_id: str) -> int:
        """Cancel the peer's requests in flight, drop what it has
        buffered at or above pool.height, and ask elsewhere at once;
        returns the number of requests taken back."""
        peer = self.peers.get(peer_id)
        heights = list(peer.inflight) if peer is not None else []
        for h in heights:
            # the requester sees it no longer owns the height and
            # leaves the accounting alone (_fetch's finally)
            task = self._tasks.pop(h, None)
            if task is not None:
                task.cancel()
            self._settle(peer, h)
        if peer is not None:
            peer.redone += len(heights)
        held = [
            h for h, (_, pid) in self.blocks.items()
            if pid == peer_id and h >= self.height
        ]
        for h in held:
            del self.blocks[h]
        for h in heights + held:
            self._maybe_spawn(h)
        self._note_head()
        return len(heights)

    def _prune_bans(self, now: float) -> None:
        """Expired bans are deleted, not just ignored — long syncs churn
        through many one-shot peer ids and the dict must not grow with
        every peer ever banned."""
        for pid in [p for p, t in self.banned_until.items() if t <= now]:
            del self.banned_until[pid]

    def banned_peers(self) -> List[str]:
        """Currently-banned peer ids (introspection for checkers)."""
        now = _now()
        self._prune_bans(now)
        return list(self.banned_until)

    def max_peer_height(self) -> int:
        return max((p.height for p in self.peers.values()), default=0)

    def exclude_peer_for_height(self, height: int, peer_id: str) -> None:
        """Prefer other peers for this one height (no ban)."""
        self.excluded.setdefault(height, set()).add(peer_id)

    def clear_exclusions(self, height: int) -> None:
        self.excluded.pop(height, None)

    def _pick_peer(self, height: int) -> Optional[PoolPeer]:
        now = _now()
        self._prune_bans(now)
        in_range = [p for p in self.peers.values() if p.serves(height)]
        candidates = [
            p
            for p in in_range
            if p.peer_id not in self.banned_until
        ]
        excl = self.excluded.get(height)
        if not candidates:
            # starvation guard: when EVERY peer serving this height is
            # banned, fetching from the least-loaded, least-recently-
            # banned one beats stalling the sync until a ban expires
            # (the liveness counterpart of the soft exclusions above);
            # the requester's failure-path sleep paces the retries.
            # Soft exclusions still steer here — a peer structurally
            # unable to serve this height (e.g. no extended commit)
            # yields to a banned-but-capable alternative
            if not in_range:
                return None
            pool = in_range
            if excl:
                pool = [p for p in in_range if p.peer_id not in excl] or in_range
            return min(
                pool,
                key=lambda p: (
                    p.pending,
                    self.banned_until.get(p.peer_id, 0.0),
                ),
            )
        if excl:
            preferred = [p for p in candidates if p.peer_id not in excl]
            if preferred:
                candidates = preferred
        # adaptive sorting: the peer that would answer soonest, by the
        # requests queued at it over the rate it has delivered at. A
        # peer with no history yet is tried (0); one that has had
        # requests pending and delivered nothing comes last; ties (a
        # join's first deal) go to the shorter queue
        clock = now - self._held_s

        def answers_in(p: PoolPeer):
            rate = p.rate_bps(clock)
            wait = 0.0 if rate is None else (
                (p.pending + 1) / rate if rate > 0 else float("inf")
            )
            return (wait, p.pending, random.random())

        return min(candidates, key=answers_in)

    # --- receive rates --------------------------------------------------

    def _sent(self, peer: PoolPeer, height: int) -> None:
        if not peer.inflight:
            peer.busy_since = _now() - self._held_s
        peer.inflight[height] = peer.mark
        peer.requests += 1

    def _received(self, peer: PoolPeer, height: int, block) -> None:
        """Count the response by its wire size (the reference's
        AddBlock(..., blockSize)) and buffer it for the verify loop."""
        size = len(getattr(block, "_raw_bytes", b""))
        peer.blocks += 1
        peer.bytes += size
        peer.biggest = max(peer.biggest, size)
        peer.mark = (_now(), peer.bytes)
        if peer.first is None:
            peer.first = peer.mark
        if height >= self.height:
            self.blocks[height] = (block, peer.peer_id)
            if len(self.blocks) > self.blocks_hwm:
                self.blocks_hwm = len(self.blocks)
            self._note_head()
        self._new_block.set()

    def _settle(self, peer: PoolPeer, height: int) -> None:
        """The request is over, however it ended."""
        del peer.inflight[height]
        if not peer.inflight:
            peer.busy_s += _now() - self._held_s - peer.busy_since
            peer.busy_since = None
            peer.first = peer.mark = None  # the link may idle from here

    def _tick(self, now: float) -> None:
        """One beat of the monitor. A beat that comes late measures a
        hold of the loop (``_apply_window`` is synchronous: 0.2 s a
        window of val150, 4.3 s of qa175): that time is taken off the
        running clock, and nobody is judged on such a beat, because
        what arrived during the hold is still being handed over."""
        late = now - self._last_tick - MONITOR_TICK_S
        self._last_tick = now
        if late > MONITOR_TICK_S / 2:
            self._held_s += late
            return
        self._check_rates(now)

    def _check_rates(self, now: float) -> None:
        """The receive-rate floor. A peer is judged while its OLDEST
        request in flight waits: a peer answers in the order asked, one
        response at a time, so from the last response that came before
        that request was sent (the first since the peer had work, if
        none had come yet) its link has had work without a break, and
        the bytes received since, over the time since, are the link's
        rate: counted from a response's arrival, so the round trip is
        not in it, and with the largest response yet added for the one
        under way. Once that stretch is RATE_EVIDENCE_S long and the
        rate under MIN_RECV_RATE the peer is banned. One second: eight
        blocks of a 150-validator chain at the floor, long enough that
        a burst or a stall of a few hundred ms does not decide, short
        beside the 6 s a join's first deal would wait for a 65,536 B/s
        peer. A sound 512,000 B/s link reads 512,000 or more whatever
        its round trip, and a peer asked one block at a time is never
        judged (each request is settled within a round trip), so
        latency alone never reads as a slow link. Wall time, not the
        running clock: on a beat that came on time (_tick) the request
        is known to be still unanswered, so the link worked through
        every hold since. A peer that delivered nothing in that time is
        the timeout's case (the reference leaves a rate of 0 to it
        too)."""
        for peer in list(self.peers.values()):
            if not peer.inflight or peer.peer_id in self.banned_until:
                continue
            mark = next(iter(peer.inflight.values()))
            since, bytes_then = mark or peer.first or (now, 0)
            span = now - since
            got = peer.bytes - bytes_then
            if (
                span >= RATE_EVIDENCE_S
                and 0 < got
                and got + peer.biggest < MIN_RECV_RATE * span
            ):
                self.ban_peer(peer.peer_id, "rate", got / span, since=since)

    async def _monitor(self) -> None:
        self._last_tick = _now()
        while not self._stopped:
            await asyncio.sleep(MONITOR_TICK_S)
            self._tick(_now())

    # --- requesters ---------------------------------------------------
    #
    # max_pending is an instance attribute so the reactor can raise it
    # to cover its verify-window LOOKAHEAD: the pipelined dispatch
    # needs ~2x verify_window buffered blocks or the next-window
    # pre-dispatch never has a tail to work with (found empirically:
    # a 128-wide bench replay had predispatched=0 with the fixed
    # 64-deep pool).

    def start_requesters(self) -> None:
        top = min(
            self.height + self.max_pending - 1, self.max_peer_height()
        )
        for h in range(self.height, top + 1):
            self._maybe_spawn(h)

    def _maybe_spawn(self, height: int) -> None:
        if (
            self._stopped
            or height in self.blocks
            or height in self._tasks
            or height < self.height
            or height > self.max_peer_height()
            or height >= self.height + self.max_pending
        ):
            return
        self._tasks[height] = asyncio.create_task(self._fetch(height))
        if self._monitor_task is None:
            self._monitor_task = asyncio.create_task(self._monitor())

    async def _fetch(self, height: int) -> None:
        me = asyncio.current_task()
        try:
            while not self._stopped:
                peer = self._pick_peer(height)
                if peer is None:
                    # nobody serves the height: wait for a peer to
                    # appear or to grow, not for a clock
                    self._peers_changed.clear()
                    await self._peers_changed.wait()
                    continue
                self._sent(peer, height)
                try:
                    block = await asyncio.wait_for(
                        peer.client.request_block(height), REQUEST_TIMEOUT_S
                    )
                    if block is None:
                        raise PeerError(peer.peer_id, f"no block {height}")
                    self._received(peer, height, block)
                    return
                except asyncio.CancelledError:
                    raise
                except Exception as e:
                    if self._tasks.get(height) is not me:
                        return  # taken back: the height is another task's
                    # any client failure (timeout, missing block, broken
                    # transport) bans the peer and retries elsewhere;
                    # the requester itself must never die silently. The
                    # sleep paces retries when the starvation guard
                    # keeps handing back a banned, fast-failing peer
                    traceback.print_exc()
                    timed_out = isinstance(e, asyncio.TimeoutError)
                    peer.timeouts += timed_out
                    # settled first: the ban takes back what is still
                    # in flight at the peer, and this request is over
                    self._settle(peer, height)
                    self.ban_peer(
                        peer.peer_id, "timeout" if timed_out else "failed"
                    )
                    await asyncio.sleep(0.05)
                finally:
                    # a request that a ban or a removal took back was
                    # settled there, and the height is another task's
                    if self._tasks.get(height) is me and height in peer.inflight:
                        self._settle(peer, height)
        finally:
            if self._tasks.get(height) is me:
                self._tasks.pop(height, None)

    # --- ordered consumption ------------------------------------------

    def _gap(self) -> Optional[int]:
        """The height the verify loop waits for: a window is two blocks
        at the least (block h is verified by h+1's commit), so the
        first of pool.height and its successor that is not buffered."""
        for h in (self.height, self.height + 1):
            if h not in self.blocks:
                return h
        return None

    def _note_head(self) -> None:
        """Keep the head-of-line account: the loop waits for one height
        while later ones sit buffered."""
        gap = self._gap()
        waiting = gap is not None and len(self.blocks) > gap - self.height
        if waiting and self._head_wait_since is None:
            self._head_wait_since = _now()
            self.head_waits += 1
        elif not waiting and self._head_wait_since is not None:
            self.head_wait_s += _now() - self._head_wait_since
            self._head_wait_since = None

    def head_peer(self) -> str:
        """The peer the height the loop waits for is in flight at (""
        if at none)."""
        gap = self._gap()
        for p in self.peers.values():
            if gap in p.inflight:
                return p.peer_id
        return ""

    def peek_window(self, n: int) -> List[Tuple[int, object, str]]:
        """Contiguous run of up to n+1 buffered blocks from pool.height
        (for coalesced commit verification across heights)."""
        out = []
        h = self.height
        while len(out) <= n and h in self.blocks:
            blk, pid = self.blocks[h]
            out.append((h, blk, pid))
            h += 1
        return out

    def pop_request(self) -> None:
        self.blocks.pop(self.height, None)
        self.height += 1
        self._note_head()
        self.start_requesters()

    def redo_request(self, height: int, ban_peer: Optional[str]) -> None:
        """Invalid block: drop it + all buffered blocks from its peer,
        ban the peer, refetch (reference pool.go
        RemovePeerAndRedoAllPeerRequests)."""
        if ban_peer:
            self.ban_peer(ban_peer, "bad_block")
        self.blocks.pop(height, None)
        self._note_head()
        self.start_requesters()

    def queue_stats(self) -> dict:
        """Pending-window backpressure (obs/queues.py registry). A
        FULL window is normal flow control while syncing, so the
        bound is reported as a soft target, not "maxsize" (which
        would trip the health route's full-queue degraded check)."""
        return {
            "depth": len(self.blocks),
            "high_watermark": self.blocks_hwm,
            "dropped": 0,
            "window_target": self.max_pending,
        }

    def stats(self) -> dict:
        """What each peer was asked and delivered, and how long the
        window's head was the one block missing."""
        now = _now()
        clock = now - self._held_s
        head_wait_s = self.head_wait_s
        if self._head_wait_since is not None:
            head_wait_s += now - self._head_wait_since
        return {
            "peers": {
                p.peer_id: {
                    "requests": p.requests,
                    "blocks": p.blocks,
                    "bytes": p.bytes,
                    "rate_bps": p.rate_bps(clock),
                    "timeouts": p.timeouts,
                    "banned": p.banned,
                    "redone": p.redone,
                }
                for p in self.peers.values()
            },
            "head_waits": self.head_waits,
            "head_wait_s": head_wait_s,
        }

    def is_caught_up(self) -> bool:
        """Reference blocksync/pool.go:227 IsCaughtUp: at least one
        peer (peers only exist once their status arrived, so heights
        are known), and our chain reaches maxPeerHeight-1 (block H
        needs H+1's commit to verify)."""
        if not self.peers:
            return False
        mx = self.max_peer_height()
        return mx == 0 or self.height >= mx - 1

    async def wait_for_block(self, timeout: float = 0.2) -> None:
        try:
            await asyncio.wait_for(self._new_block.wait(), timeout)
        except asyncio.TimeoutError:
            pass
        self._new_block.clear()

    def stop(self) -> None:
        self._stopped = True
        for t in self._tasks.values():
            t.cancel()
        self._tasks.clear()
        if self._monitor_task is not None:
            self._monitor_task.cancel()
            self._monitor_task = None
