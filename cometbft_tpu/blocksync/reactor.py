"""Blocksync reactor: the catch-up verify/apply loop.

Parity with reference blocksync/reactor.go poolRoutine (:560-700), with
the TPU-native twist: instead of verifying one commit at a time
(VerifyCommit at :631), the loop coalesces a WINDOW of buffered heights
and verifies all their commits in one signature-lane dispatch
(types.verify_commits_coalesced) — the north-star 10k-block replay
amortizes ~window x validators signatures per XLA call. Invalid windows
fall back to per-height verification to pinpoint the bad peer.

Block h is verified using block (h+1).LastCommit, i.e. a window of K
applies needs K+1 buffered blocks, exactly like PeekTwoBlocks in the
reference but K-wide.
"""

from __future__ import annotations

import asyncio
import time
import traceback
from typing import Callable, Optional

from .. import types as T
from ..trace import NOOP as TRACE_NOOP
from ..types.validation import (
    verify_commits_coalesced_async,
)
from ..utils import codec
from ..utils.log import get_logger
from .pool import BlockPool

_log = get_logger("blocksync")

VERIFY_WINDOW = 32
SWITCH_TO_CONSENSUS_INTERVAL_S = 1.0
# Apply a block without its extended commit after this many fetches of
# the height came back EC-less (liveness: no reachable peer may hold
# the EC — see _check_extended_commit).
EC_MISS_TOLERANCE = 2


class MissingExtendedCommit(ValueError):
    """Peer served a block without an EC at an extension-enabled
    height: possibly an honest gap, never a verification failure."""


class _PrefixErrors:
    """First ``n`` per-job errors of a wider coalesced handle (the
    lookahead covered more heights than this pass applies)."""

    __slots__ = ("_h", "_n")

    def __init__(self, handle, n: int) -> None:
        self._h = handle
        self._n = n

    def result(self):
        return self._h.result()[: self._n]


class _SplicedErrors:
    """Lookahead verdicts for the first ``n`` jobs + a fresh dispatch
    for the remainder, in job order (the pool refilled after the
    lookahead was sized)."""

    __slots__ = ("_a", "_b", "_n")

    def __init__(self, pre, rest, n: int) -> None:
        self._a = pre
        self._b = rest
        self._n = n

    def result(self):
        return self._a.result()[: self._n] + self._b.result()


class BlockSyncReactor:
    def __init__(
        self,
        state,
        block_exec,
        block_store,
        pool: Optional[BlockPool] = None,
        signature_cache: Optional[T.SignatureCache] = None,
        on_caught_up: Optional[Callable] = None,
        block_ingestor=None,  # fork: adaptive sync ingest hook
        verify_window: int = VERIFY_WINDOW,
        local_blocks_chain=None,  # fn(state)->bool, reactor.go:448
    ):
        self.state = state
        self.block_exec = block_exec
        self.block_store = block_store
        self.pool = pool or BlockPool(state.last_block_height + 1)
        # the pipelined verify needs ~2x the verify window buffered
        # (current window + pre-dispatched lookahead + the +1 commit
        # block); a pool shallower than that silently disables the
        # overlap (see pool.start_requesters)
        self.pool.max_pending = max(
            self.pool.max_pending, 2 * verify_window + 2
        )
        self.sig_cache = signature_cache or T.SignatureCache()
        self.on_caught_up = on_caught_up
        self.ingestor = block_ingestor
        self.window = verify_window
        self.local_blocks_chain = local_blocks_chain
        self.blocks_applied = 0
        # height -> set of peer ids that served the height EC-less
        self._ec_misses: dict = {}
        # pipelined verify: (key, handle) for the NEXT window's
        # already-dispatched signature batch (see _process_window)
        self._inflight = None
        self.pipeline_stats = {
            "reused": 0,        # pre-dispatched handles consumed
            "dispatched": 0,    # fresh (non-pipelined) dispatches
            "predispatched": 0, # lookahead dispatches issued
            "discarded": 0,     # handles dropped (redo/valset/reshuffle)
        }
        self._task: Optional[asyncio.Task] = None
        self._stopped = False
        # tracing plane (trace/): node wiring swaps in the per-node
        # tracer (the pool's bans land on it too); last_window_bps
        # feeds the Prometheus window-throughput gauge
        # (utils/metrics.py)
        self.tracer = TRACE_NOOP
        self.last_window_bps = 0.0

    @property
    def tracer(self):
        return self.pool.tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        self.pool.tracer = tracer

    # --- lifecycle ----------------------------------------------------

    async def start(self) -> None:
        self.pool.start_requesters()
        self._task = asyncio.create_task(self._pool_routine())

    async def stop(self) -> None:
        self._stopped = True
        self.pool.stop()
        if self._task:
            self._task.cancel()
            try:
                # bounded (ASY110): the pool routine may be awaiting
                # an executor-parked verify — abandon it past budget
                await asyncio.wait_for(self._task, 10.0)
            except asyncio.TimeoutError:
                pass
            except asyncio.CancelledError:
                if not self._task.cancelled():
                    raise  # outer cancel of stop() itself: propagate
            except Exception:
                traceback.print_exc()

    # --- the verify/apply loop ----------------------------------------

    async def _pool_routine(self) -> None:
        last_switch_check = time.monotonic()
        while not self._stopped:
            if time.monotonic() - last_switch_check > SWITCH_TO_CONSENSUS_INTERVAL_S:
                last_switch_check = time.monotonic()
                # switch when caught up, OR when blocksync cannot
                # proceed without our own votes (we hold >=1/3 power,
                # reference reactor.go:543 + localNodeBlocksTheChain)
                if self.pool.is_caught_up() or (
                    self.local_blocks_chain is not None
                    and self.local_blocks_chain(self.state)
                ):
                    _log.info(
                        "caught up, leaving blocksync",
                        height=self.state.last_block_height,
                        applied=self.blocks_applied,
                    )
                    if self.on_caught_up:
                        self.on_caught_up(self.state)
                    return
            # peek one extra window of lookahead: _process_window
            # pre-dispatches the NEXT window's signature batch before
            # applying the current one (device work overlaps host
            # decode/apply — docs/PERF.md "overlapped replay dispatch")
            window = self.pool.peek_window(self.window * 2)
            if len(window) < 2:
                # held across the await (like verify_wait below): the
                # loop's wait for the peer, which no window span covers.
                # With blocks buffered it is a wait for the HEAD, at the
                # peer named: the window's other heights have come
                sp = self.tracer.annotated_span(
                    "blocksync.window.fetch_wait", tid="blocksync",
                    buffered=len(self.pool.blocks),
                    head_peer=self.pool.head_peer(),
                )
                try:
                    await self.pool.wait_for_block()
                finally:
                    sp.end()
                continue
            try:
                if self.ingestor is None:
                    # overlapped path: the blocking verify wait runs
                    # in an executor, so the loop stays responsive
                    # (and window K's host apply overlaps window
                    # K+1's pool verification — docs/PERF.md host
                    # plane)
                    applied = await self._process_window_overlapped(
                        window
                    )
                else:
                    # adaptive mode: consensus shares this loop, and
                    # the blocking pass serializes against it — an
                    # await inside the pass would let consensus
                    # commit mid-window against the pass's state view
                    applied = self._process_window(window)
            except asyncio.CancelledError:
                raise
            except Exception:
                traceback.print_exc()
                applied = 0
            if applied == 0:
                await asyncio.sleep(0.01)
            await asyncio.sleep(0)  # yield

    def _process_window(self, window) -> int:
        """Verify all verifiable heights in the window with ONE batch
        dispatch, then apply them in order. Returns #applied.

        Blocking form (tests, adaptive/ingestor mode); the pool
        routine's plain path goes through _process_window_overlapped,
        which parks the verify wait in an executor instead."""
        t0 = time.monotonic()
        with self.tracer.annotated_span(
            "blocksync.window.prepare", tid="blocksync"
        ):
            prep = self._prepare_window(window)
        if prep is None:
            return 0
        window, jobs, handle = prep
        with self.tracer.annotated_span(
            "blocksync.window.verify_wait", tid="blocksync",
            jobs=len(jobs),
        ):
            errors = handle.result()
        pre = self._predispatch_lookahead(len(jobs))
        with self.tracer.annotated_span(
            "blocksync.window.apply", tid="blocksync", jobs=len(jobs)
        ):
            applied = self._apply_window(window, jobs, errors, pre)
        self._observe_window(applied, time.monotonic() - t0)
        return applied

    async def _process_window_overlapped(self, window) -> int:
        """Same pass as _process_window, but the blocking verify wait
        runs in the default executor: the event loop keeps serving
        peer fetches/heartbeats while the parallel host plane (or the
        device) chews on the window's signatures, and the lookahead
        window pre-dispatched by _prepare_window verifies on pool
        threads WHILE this pass's host apply runs — overlap with no
        device required."""
        t0 = time.monotonic()
        with self.tracer.annotated_span(
            "blocksync.window.prepare", tid="blocksync"
        ):
            prep = self._prepare_window(window)
        if prep is None:
            return 0
        window, jobs, handle = prep
        # the executor-parked wait is where the verify plane's wall
        # hides (PR 3): its span length vs apply's is the overlap
        sp = self.tracer.annotated_span(
            "blocksync.window.verify_wait", tid="blocksync",
            jobs=len(jobs),
        )
        try:
            errors = await asyncio.get_running_loop().run_in_executor(
                None, handle.result
            )
        finally:
            sp.end()
        pre = self._predispatch_lookahead(len(jobs))
        with self.tracer.annotated_span(
            "blocksync.window.apply", tid="blocksync", jobs=len(jobs)
        ):
            applied = self._apply_window(window, jobs, errors, pre)
        self._observe_window(applied, time.monotonic() - t0)
        return applied

    def _prepare_window(self, window):
        """Dispatch (or reuse) the window's coalesced signature batch.
        Returns None when nothing is verifiable this pass, else
        (window, jobs, handle). The lookahead is NOT dispatched here:
        the caller issues _predispatch_lookahead after this handle's
        verdicts resolve, when the pool reflects the refill that
        happened during the wait.

        The batch uses the CURRENT state's validator set, so it must
        stop at the first height whose header advertises a different
        validators_hash (valset change mid-window): those heights are
        verified on a later pass once the state has advanced. The hash
        is only used to LIMIT the batch — each block is still fully
        validated against the locally-derived valset when applied."""
        if self.ingestor is not None:
            # adaptive mode: consensus may ALSO be committing heights
            # (its own rounds / commit_block catch-up). Track its state
            # and drop heights it already owns, else the window would
            # verify against a stale valset and ban honest peers.
            self.state = self.ingestor.state
            while window and window[0][0] < self.ingestor.rs.height:
                self.pool.pop_request()
                self.blocks_applied += 1
                window = window[1:]
            if len(window) < 2:
                return None
        # take (and clear) the pre-dispatched handle FIRST: every exit
        # from this pass either consumes it or drops it — a handle
        # must never survive a pass whose window it was not checked
        # against (e.g. the head-mismatch refetch below)
        inflight, self._inflight = self._inflight, None
        # block at window[i] is verified by window[i+1].last_commit
        vals_hash = self.state.validators.hash()
        jobs, key = self._build_jobs(window, vals_hash, self.window - 1)
        if not jobs:
            if inflight is not None:
                self.pipeline_stats["discarded"] += 1
            if len(window) >= 1:
                # head block claims a different valset than our state
                # derives -> it cannot validate; refetch elsewhere
                h, _, peer = window[0]
                self.pool.redo_request(h, peer)
            return None
        # Pipelined verify: reuse the handle pre-dispatched on the
        # previous pass when its inputs CONTENT-match this window —
        # the key is content-based (valset hash + every involved
        # block's hash), so redo/ban refetches, valset changes and
        # pool reshuffles all miss it and a wrong verdict can never
        # be consumed. Length drift (the pool refills between the
        # lookahead peek and this pass) reuses the matching prefix
        # and dispatches only the remainder (_reuse_inflight).
        handle = (
            self._reuse_inflight(inflight, jobs, key)
            if inflight is not None
            else None
        )
        if handle is None:
            if inflight is not None:
                self.pipeline_stats["discarded"] += 1
            handle = verify_commits_coalesced_async(
                self.state.chain_id,
                jobs,
                cache=self.sig_cache,
                priority=T.PRIORITY_CATCHUP,
            )
            self.pipeline_stats["dispatched"] += 1
        return window, jobs, handle

    def _reuse_inflight(self, inflight, jobs, key):
        """Content-match the pre-dispatched handle against this
        pass's jobs, tolerating LENGTH drift in either direction
        (each coalesced job is independent, so verdict prefixes
        compose):

        - lookahead ⊇ window: consume the prefix of its verdicts;
        - lookahead ⊂ window (the pool refilled after the lookahead
          peek): consume ALL its verdicts and dispatch a fresh batch
          for just the remainder, spliced in order.

        Any content mismatch — a refetched block, a valset change —
        returns None and the caller drops the handle. Returns a
        result()-bearing handle or None."""
        pre_key, pre_handle = inflight
        if pre_key[0] != key[0]:
            return None
        pre_hs, hs = pre_key[1], key[1]
        if len(hs) <= len(pre_hs):
            if pre_hs[: len(hs)] != hs:
                return None
            self.pipeline_stats["reused"] += 1
            if len(hs) == len(pre_hs):
                return pre_handle
            return _PrefixErrors(pre_handle, len(hs) - 1)
        if hs[: len(pre_hs)] != pre_hs:
            return None
        n_pre = len(pre_hs) - 1
        rest_handle = verify_commits_coalesced_async(
            self.state.chain_id,
            jobs[n_pre:],
            cache=self.sig_cache,
            priority=T.PRIORITY_CATCHUP,
        )
        self.pipeline_stats["reused"] += 1
        self.pipeline_stats["dispatched"] += 1
        return _SplicedErrors(pre_handle, rest_handle, n_pre)

    def _predispatch_lookahead(self, n_skip: int):
        """Dispatch the NEXT window's batch before applying this one:
        the verification plane (device, or the host pool) chews on
        window K+1 while the host decodes/applies window K
        (docs/PERF.md "overlapped replay dispatch"). Peeked FRESH
        here — after this window's verdicts resolved — so the
        lookahead covers the blocks the requesters pulled in WHILE
        the verify was pending; peeking at prepare time instead sizes
        the lookahead to the pre-refill pool and the next pass's
        (longer) window misses the content key on every pass. Built
        against the pre-apply valset — sound because only heights
        whose headers claim the SAME validators_hash enter a batch,
        and the reuse key check re-validates against the post-apply
        state before any verdict is consumed."""
        tail = self.pool.peek_window(self.window * 2)[n_skip:]
        if len(tail) < 2:
            return None
        pre_jobs, pre_key = self._build_jobs(
            tail, self.state.validators.hash(), self.window - 1
        )
        if not pre_jobs:
            return None
        self.pipeline_stats["predispatched"] += 1
        return (
            pre_key,
            verify_commits_coalesced_async(
                self.state.chain_id,
                pre_jobs,
                cache=self.sig_cache,
                priority=T.PRIORITY_CATCHUP,
            ),
        )

    def _canonical_parts(self, blk, nxt):
        """Part set for ``blk`` — from the peer's wire bytes when they
        produce the part-set header the validators actually signed
        (saves a full re-encode), else from our canonical encoding.

        A peer could serve a NON-canonical encoding of the same block
        (permissive parse) to poison the store; on mismatch every
        memoized wire-bytes shortcut downstream (store save_block
        persists commit._raw_bytes for SC:/C: records) must re-encode
        canonically too, so the memos are dropped."""
        signed_psh = nxt.last_commit.block_id.part_set_header
        raw = getattr(blk, "_raw_bytes", None)
        if raw is not None:
            parts = T.PartSet.from_data(raw)
            if parts.header.hash == signed_psh.hash:
                return parts
            for o in (blk, blk.last_commit):
                if hasattr(o, "_raw_bytes"):
                    del o._raw_bytes
        return T.PartSet.from_data(codec.encode_block(blk))

    def _apply_window(self, window, jobs, errors, pre) -> int:
        """Apply the window's verified blocks in order; returns
        #applied. ``errors`` are the per-job verdicts from the
        coalesced batch (resolved by the caller, possibly in an
        executor)."""
        # Stage the window's store writes and flush them in ONE
        # db.write_batch BEFORE any apply: the commit batch already
        # vouched for every staged block (errors[i] is None ⇒ +2/3 of
        # the valset signed this exact content), and store-ahead-of-
        # state is the crash direction the handshake replays back
        # (consensus/replay.py) — whereas deferring writes past the
        # applies would leave the state ahead of the store, which no
        # recovery path handles. A block that later fails
        # validate_block (a fork — the reference panics there) stays
        # persisted; the refetch loop skips re-saving via the height
        # guard below, and content is hash-pinned by the commit either
        # way. The ingestor path owns its own persistence.
        parts_by_idx = {}
        ec_by_idx = {}
        if self.ingestor is None:
            entries = []
            for i in range(len(jobs)):
                if errors[i] is not None:
                    break
                h, blk, peer_i = window[i]
                _, nxt, _ = window[i + 1]
                parts = self._canonical_parts(blk, nxt)
                parts_by_idx[i] = parts
                # the EC requirement gates persistence: a block whose
                # extended commit is missing/invalid must never enter
                # the store bare (a node serving a bare tip block
                # stalls future joiners — the exact property the
                # at-tip refusal below protects)
                enabled = (
                    self.state.consensus_params.vote_extensions_enabled(
                        h
                    )
                )
                try:
                    ec_bytes = self._check_extended_commit(
                        h, blk, peer_i
                    )
                except Exception:
                    # missing/invalid EC: the apply loop below re-runs
                    # the check at this height and owns the tolerance/
                    # redo logic; nothing at or past it is staged
                    break
                ec_by_idx[i] = (enabled, ec_bytes)
                if self.block_store.height() < h:
                    entries.append((blk, parts, nxt.last_commit))
            if entries:
                with self.tracer.annotated_span(
                    "blocksync.window.persist", tid="blocksync",
                    blocks=len(entries),
                ):
                    self.block_store.save_block_batch(entries)
        applied = 0
        for i, _job in enumerate(jobs):
            h, blk, peer = window[i]
            _, nxt, _ = window[i + 1]
            if errors[i] is not None:
                # bad commit: could be a corrupt block h (its hash feeds
                # the expected BlockID) OR a corrupt h+1.LastCommit ->
                # ban BOTH senders and refetch, like the reference's
                # handleValidationFailure (blocksync/reactor.go:749).
                _log.error(
                    "commit verification failed, refetching",
                    height=h,
                    peer=str(peer)[:12],
                    err=repr(errors[i]),
                )
                self.pool.redo_request(h, peer)
                if window[i + 1][2] != peer:
                    self.pool.redo_request(h + 1, window[i + 1][2])
                break
            bid = jobs[i][1]
            try:
                self.block_exec.validate_block(
                    self.state, blk, skip_commit_check=True
                )
            except Exception:
                self.pool.redo_request(h, peer)
                break
            try:
                cached = ec_by_idx.get(i)
                if cached is not None and cached[0] == (
                    self.state.consensus_params.vote_extensions_enabled(
                        h
                    )
                ):
                    # verified during window staging, and the
                    # enablement the check assumed still holds under
                    # the evolved state
                    ec_bytes = cached[1]
                else:
                    if cached is not None:
                        # consensus params moved mid-window: the
                        # staged flush persisted this height (and the
                        # rest of the window) under an enablement
                        # that no longer holds — roll the UNAPPLIED
                        # store tip back to h-1 before re-deciding,
                        # so a block whose EC requirement just
                        # flipped on can never outlive this pass bare
                        # (the heights removed are exactly the
                        # staged-not-yet-applied ones; re-applies
                        # fall back to per-block save below)
                        while self.block_store.height() >= h:
                            self.block_store.delete_latest_block()
                    # not staged (an EC decision was pending at this
                    # height) or params moved: run the full check
                    # against the CURRENT state
                    ec_bytes = self._check_extended_commit(
                        h, blk, peer
                    )
            except MissingExtendedCommit as e:
                served = self._ec_misses.setdefault(h, set())
                served.add(peer)
                # Bare-apply rules (the reference hard-rejects EC-less
                # blocks everywhere, blocksync/reactor.go:618-648; we
                # tolerate narrowly for liveness):
                #  - NEVER at the pool's max height — that block is the
                #    switch-to-consensus tip, and a node that applied
                #    it bare cannot propose at tip+1 (no EC to carry)
                #    nor serve the EC to later joiners;
                #  - only after EC_MISS_TOLERANCE *distinct* peers came
                #    back bare (a single byzantine peer that wins every
                #    refetch must not be able to force a bare apply),
                #    or every known peer has (single-peer nets can
                #    never reach the distinct-peer bar).
                # the highest height blocksync can apply is
                # max_peer_height - 1 (block h needs h+1's commit), and
                # is_caught_up switches to consensus there — so THAT is
                # the tip to protect
                at_tip = h >= self.pool.max_peer_height() - 1
                # exhaustion counts only peers whose advertised range
                # can actually serve h — lagging or pruned peers in the
                # denominator would make exhaustion unreachable and
                # stall the sync below tip forever
                can_serve = {
                    pid
                    for pid, p in self.pool.peers.items()
                    if p.base <= h <= p.height
                }
                exhausted = bool(can_serve) and served >= can_serve
                if at_tip or (
                    len(served) < EC_MISS_TOLERANCE and not exhausted
                ):
                    # honest peers can lack the EC: refetch WITHOUT
                    # banning, steering the retry to a DIFFERENT peer
                    # (soft exclusion — the fastest peer would
                    # otherwise be re-picked and win the refetch too)
                    _log.info(
                        "peer lacks extended commit, refetching",
                        height=h,
                        distinct_peers=len(served),
                        at_tip=at_tip,
                    )
                    self.pool.exclude_peer_for_height(h, peer)
                    self.pool.redo_request(h, None)
                    break
                _log.info(
                    "applying historical block without extended commit",
                    height=h,
                    distinct_peers=len(served),
                )
                ec_bytes = None
            except Exception as e:
                _log.error(
                    "extended commit check failed, refetching",
                    height=h,
                    err=repr(e),
                )
                self.pool.redo_request(h, peer)
                break
            # persist the verified EC immediately: every later branch
            # (incl. "consensus ingested it concurrently") must leave
            # this node able to SERVE the EC, or a future joiner stalls
            # on "peer omitted extended commit"
            if ec_bytes and not self.block_store.load_extended_commit(h):
                self.block_store.save_extended_commit(h, ec_bytes)
            parts = parts_by_idx.get(i)
            if parts is None:
                parts = self._canonical_parts(blk, nxt)
            if self.ingestor is not None:
                # fork: adaptive sync — pipeline the verified block
                # straight into the consensus state machine. The
                # ingestor applies the block and returns the post-apply
                # state so subsequent window validation isn't stale.
                if blk.height < self.ingestor.rs.height:
                    # consensus ingested it concurrently (catch-up)
                    self.state = self.ingestor.state
                    self.pool.pop_request()
                    self.blocks_applied += 1
                    applied += 1
                    continue
                try:
                    self.state = self.ingestor.ingest_verified_block(
                        blk, parts, nxt.last_commit
                    )
                except ValueError:
                    # consensus is mid-commit at this height; let it
                    # finish and resume on the next pass
                    break
            else:
                # usually persisted by the window-batch flush above
                # (or an earlier pass); blocks at/behind an EC
                # decision made during THIS loop (e.g. a tolerated
                # bare apply) were not staged — persist individually
                if self.block_store.height() < h:
                    self.block_store.save_block(
                        blk, parts, nxt.last_commit
                    )
                self.state = self.block_exec.apply_verified_block(
                    self.state, bid, blk
                )
            if h in self._ec_misses:
                del self._ec_misses[h]
                self.pool.clear_exclusions(h)
            self.pool.pop_request()
            self.blocks_applied += 1
            applied += 1
        else:
            # every job applied without a redo/ban/ingest break: the
            # pre-dispatched next-window handle stays valid for reuse
            # on the next pass (subject to the key re-check). On ANY
            # break the handle is dropped — its blocks may be
            # refetched or the valset may have moved.
            self._inflight = pre
        if pre is not None and self._inflight is not pre:
            self.pipeline_stats["discarded"] += 1
        return applied

    def _observe_window(self, applied: int, wall_s: float) -> None:
        """Per-window throughput: a counter event on the trace
        timeline + the live value the Prometheus gauge reads."""
        if applied <= 0 or wall_s <= 0:
            return
        bps = applied / wall_s
        self.last_window_bps = bps
        self.tracer.counter(
            "blocksync.window_blocks_per_s", round(bps, 1),
            tid="blocksync",
        )

    def _build_jobs(self, window, vals_hash, max_jobs: int):
        """Verify jobs for the leading valset-constant prefix of
        ``window`` (block i verified by block i+1's last_commit,
        PeekTwoBlocks K-wide), plus a reuse key identifying the exact
        inputs BY CONTENT: the valset hash and every involved block's
        hash (the hash covers the header, whose last_commit_hash binds
        the commit the job verifies). Content keys make refetches safe
        — a replaced block hashes differently, so a pre-dispatched
        handle can never be replayed against different inputs, while a
        content-identical refetch may still reuse it."""
        jobs = []
        for i in range(min(len(window) - 1, max_jobs)):
            h, blk, peer = window[i]
            _, nxt, _ = window[i + 1]
            if blk.header.validators_hash != vals_hash:
                break
            bid = T.BlockID(
                blk.hash(),
                nxt.last_commit.block_id.part_set_header,
            )
            jobs.append(
                (self.state.validators, bid, h, nxt.last_commit)
            )
        key = (
            vals_hash,
            tuple(
                bytes(window[i][1].hash()) for i in range(len(jobs) + 1)
            )
            if jobs
            else (),
        )
        return jobs, key

    def _check_extended_commit(self, h, blk, peer):
        """When vote extensions are enabled at height h the peer SHOULD
        supply a valid extended commit with the block (reference
        blocksync/reactor.go:648): commit sigs verify against the
        valset, extension signatures verify per lane, and the payload
        binds to this block. Returns the raw bytes to persist (or None
        when extensions are disabled).

        A peer that simply LACKS the EC is distinguished from one that
        sent an invalid EC: an honest node may legitimately hold a
        block without its EC (e.g. it tolerated missing ECs itself
        while syncing before this fix existed, or pruned them), so a
        missing payload raises MissingExtendedCommit — retried without
        banning, and tolerated once EC_MISS_TOLERANCE distinct fetches
        came back bare (otherwise a network where no reachable peer
        holds the EC for one height would stall blocksync forever)."""
        enabled = self.state.consensus_params.vote_extensions_enabled(h)
        ec_bytes = getattr(blk, "_ec_bytes", None)
        if not enabled:
            return None  # ignore unsolicited payloads
        if not ec_bytes:
            raise MissingExtendedCommit(
                "peer omitted extended commit at extension-enabled "
                f"height {h}"
            )
        ec = codec.decode_extended_commit(ec_bytes)
        T.verify_extended_commit(
            self.state.chain_id,
            self.state.validators,
            blk.hash(),
            h,
            ec,
            cache=self.sig_cache,
            priority=T.PRIORITY_CATCHUP,
        )
        return ec_bytes
