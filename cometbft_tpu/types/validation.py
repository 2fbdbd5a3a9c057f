"""Commit verification — the seam every sync path funnels through.

Parity with reference types/validation.go: VerifyCommit (:30),
VerifyCommitLight (:65), VerifyCommitLightTrusting (:148), the
``*AllSignatures`` and ``*WithCache`` variants. Consumers: blocksync replay, adaptive
ingest, light-client bisection, evidence checks (SURVEY.md §2.3).

TPU-first departure: the reference dispatches between a sequential path
and a random-linear-combination CPU batch; here every multi-signature
verification builds one lane batch and submits it as one ticket of the
verify scheduler (crypto/scheduler.py; crypto/batch.decide routes it to
the TPU kernel or the host plane), which returns per-lane verdicts — the
"light" early-exit at +2/3 is pointless on SIMD lanes, so light mode
just restricts *which* signatures are checked (the ones counted toward
the tally), identically to the reference's semantics.
"""

from __future__ import annotations

from bisect import bisect_right
from fractions import Fraction
from itertools import accumulate, compress, islice
from typing import Optional

import numpy as np

from ..crypto import scheduler as crypto_sched
from ..crypto.lanes import LaneBatch
from ..crypto.scheduler import (  # re-exported: consumers pass these
    PRIORITY_CATCHUP,
    PRIORITY_LIGHT,
    PRIORITY_LIVE,
)
from ..trace import global_tracer
from .block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BlockID,
    Commit,
)
from .canonical import (
    PRECOMMIT_TYPE,
    finish_vote_sign_bytes,
    vote_sign_bytes_parts,
)
from .signature_cache import SignatureCache
from .validator_set import ValidatorSet


# span row of the seam's two stages: whichever thread calls the seam
_TID_CALLER = "validation"


class CommitVerifyError(Exception):
    pass


class ErrNotEnoughVotingPower(CommitVerifyError):
    pass


class ErrInvalidSignature(CommitVerifyError):
    pass


def _commit_sign_bytes(chain_id: str, commit: Commit, cs) -> bytes:
    """Sign bytes for one CommitSig, memoized on the commit (decoded
    commits are immutable by convention, codec.decode_commit) at two
    levels: the timestamp-independent (prefix, suffix) per block-id
    flag class, and the FINISHED bytes per (flag, timestamp) —
    proposer-aligned voting makes many signatures of one commit share
    a timestamp, so a 150-signature commit often encodes once, and
    never more than once per distinct timestamp."""
    parts = getattr(commit, "_sb_parts", None)
    if parts is None:
        parts = {}
        commit._sb_parts = parts
    flag_commit = cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
    key = (chain_id, flag_commit, cs.timestamp_ns)
    sb = parts.get(key)
    if sb is None:
        pkey = (chain_id, flag_commit)
        ps = parts.get(pkey)
        if ps is None:
            ps = vote_sign_bytes_parts(
                chain_id,
                PRECOMMIT_TYPE,
                commit.height,
                commit.round,
                cs.block_id(commit.block_id),
            )
            parts[pkey] = ps
        sb = finish_vote_sign_bytes(ps[0], ps[1], cs.timestamp_ns)
        parts[key] = sb
    return sb


def _basic_checks(
    vals: ValidatorSet, commit: Commit, height: int, block_id: Optional[BlockID]
) -> None:
    if commit is None:
        raise CommitVerifyError("nil commit")
    if vals.size() != commit.size():
        raise CommitVerifyError(
            f"validator set size {vals.size()} != commit size {commit.size()}"
        )
    if height != commit.height:
        raise CommitVerifyError(
            f"height {height} != commit height {commit.height}"
        )
    if block_id is not None and block_id.key() != commit.block_id.key():
        raise CommitVerifyError("wrong BlockID in commit")


def _run_batch_async(
    items,
    cache: Optional[SignatureCache],
    priority: Optional[int] = None,
    label: str = "",
):
    """items: list of (pubkey, sign_bytes, sig), or the same lanes by
    columns (a crypto/lanes.LaneBatch, as ``_coalesce_lanes`` builds
    them). Returns a handle whose ``result()`` yields list[bool] — async
    so callers (the blocksync window pipeline) can overlap host work
    with the verification in flight.

    THE single choke point onto the unified verify scheduler
    (crypto/scheduler.py): cache-unskipped lanes are submitted as one
    ticket, in the form they came in, under the caller's priority
    class — live round > light session > catch-up/evidence (default)
    — and the scheduler takes the calibrated backend-routing decision
    from there. The handle is genuinely pending on every backend:
    device batches ride the XLA async dispatch, host-routed batches
    ride the slot-bounded chunk pipeline — either way the caller's
    decode/apply work proceeds while lanes verify (docs/PERF.md
    "Unified verify scheduler")."""
    # lanes: what the scheduler gets; to_verify: the item of each, or
    # None where every item goes, in order; keys: the cache's of each
    lanes, to_verify, keys = items, None, None
    if cache is not None:
        columnar = isinstance(items, LaneBatch)
        if columnar:
            keys = cache.keys_of_columns(items.msgs, items.sigs, items.keys)
        else:
            key = cache.key
            keys = [key(sb, sig, pk.key_bytes) for pk, sb, sig in items]
        hits = cache.contains_many(keys)
        if True in hits:
            to_verify = _falses(hits)
            lanes = (
                items.take(to_verify)
                if columnar
                else [items[i] for i in to_verify]
            )
            keys = [keys[i] for i in to_verify]
    pending = (
        crypto_sched.scheduler().submit(
            lanes,
            priority=PRIORITY_CATCHUP if priority is None else priority,
            label=label,
        )
        if len(lanes)
        else None
    )
    return _BatchHandle(items, to_verify, keys, pending, cache)


class _BatchHandle:
    """Cache-aware batch handle: ``result()`` resolves the pending
    dispatch, fills verdicts over the cache-skipped lanes, and feeds
    verified signatures back into the cache."""

    __slots__ = ("_items", "_to_verify", "_keys", "_pending", "_cache")

    def __init__(self, items, to_verify, keys, pending, cache) -> None:
        self._items = items
        self._to_verify = to_verify
        self._keys = keys
        self._pending = pending
        self._cache = cache

    @property
    def ticket_id(self) -> Optional[int]:
        """The scheduler ticket's id (the ``ticket`` of its spans), or
        None where the cache answered every lane."""
        return None if self._pending is None else self._pending.id

    def wait(self):
        """Block for the ticket's verdicts (None with no ticket)."""
        if self._pending is None:
            return None
        return self._pending.result()[1]

    def refused(self, verdicts) -> list:
        """The items the ticket refused, ascending, from its verdicts;
        the accepted lanes fed to the cache in lane order."""
        if verdicts is None:
            return []
        bad = _falses(verdicts)
        if self._cache is not None:
            keys = self._keys
            self._cache.add_many(
                list(compress(keys, verdicts)) if bad else keys
            )
        to_verify = self._to_verify
        return bad if to_verify is None else [to_verify[b] for b in bad]

    def fill(self, verdicts):
        """Per-item verdicts from the ticket's, cache fed."""
        oks = [True] * len(self._items)
        for i in self.refused(verdicts):
            oks[i] = False
        return oks

    def result(self):
        return self.fill(self.wait())


def _falses(flags) -> list:
    """Positions of the false entries of a vector (the refused lanes
    of a ticket's verdicts, a list or a bool array; the misses of a
    cache query), ascending."""
    return np.flatnonzero(~np.asarray(flags, bool)).tolist()


def _run_batch(
    items,
    cache: Optional[SignatureCache],
    priority: Optional[int] = None,
    label: str = "",
):
    """items: list of (pubkey, sign_bytes, sig). Returns list[bool]."""
    if not items:
        return []
    return _run_batch_async(
        items, cache, priority=priority, label=label
    ).result()


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    cache: Optional[SignatureCache] = None,
    priority: Optional[int] = None,
) -> None:
    """Full verification: every non-absent signature must be valid
    (including nil votes), and >2/3 of power must have signed block_id.
    (reference types/validation.go:30; used by blocksync + ingest).
    ``priority`` is the verify-scheduler class (PRIORITY_LIVE for the
    consensus hot path; default catch-up).

    One ticket of its own, so its two stages on the caller's thread
    are spans as the coalesced seam's are (docs/TRACE.md "One ticket,
    one timeline"): ``validation.commit.build`` (entry to ``submit()``
    returned) and ``validation.commit.fold`` (verdicts in hand to
    return or raise)."""
    tr = global_tracer()
    with tr.annotated_span(
        "validation.commit.build", tid=_TID_CALLER
    ) as sp:
        _basic_checks(vals, commit, height, block_id)
        items = []
        tally_idx = []
        for i, cs in enumerate(commit.signatures):  # bftlint: disable=ASY117 — verifying an O(V) commit payload is O(V) by construction; once per commit received, curve math batch-verified via the lane cache
            if cs.is_absent():
                continue
            val = vals.get_by_index(i)
            if val.address != cs.validator_address:
                raise CommitVerifyError(
                    f"commit sig {i} address mismatch with validator set"
                )
            items.append(
                (val.pub_key, _commit_sign_bytes(chain_id, commit, cs), cs.signature)
            )
            tally_idx.append(i)
        handle = _run_batch_async(
            items, cache, priority=priority, label="commit"
        )
        ticket = getattr(handle, "ticket_id", None)
        sp.set(ticket=ticket, lanes=len(items))
    if not isinstance(handle, _BatchHandle):
        # a stand-in for the batch route (tests, the benchmark's
        # control): no ticket, so no stage to record
        return _fold_commit(handle.result(), tally_idx, vals, commit)
    verdicts = handle.wait()
    with tr.annotated_span(
        "validation.commit.fold", tid=_TID_CALLER, ticket=ticket,
        lanes=len(items),
    ):
        _fold_commit(handle.fill(verdicts), tally_idx, vals, commit)


def _fold_commit(
    oks: list, tally_idx: list, vals: ValidatorSet, commit: Commit
) -> None:
    """verify_commit's verdict and tally fold: the first refused lane
    named, then the for-block power against 2/3."""
    tallied = 0
    for (i, ok) in zip(tally_idx, oks):
        if not ok:
            raise ErrInvalidSignature(f"invalid signature for validator {i}")
        cs = commit.signatures[i]
        if cs.for_block():
            tallied += vals.get_by_index(i).voting_power
    if not tallied * 3 > vals.total_voting_power() * 2:
        raise ErrNotEnoughVotingPower(
            f"tallied {tallied} <= 2/3 of {vals.total_voting_power()}"
        )


def _collect_light_lanes(
    chain_id: str,
    vals: ValidatorSet,
    block_id: Optional[BlockID],
    height: int,
    commit: Commit,
    all_signatures: bool,
    items: list,
) -> list:
    """Shared lane builder for LIGHT verification — the serial path
    and the coalesced jobs path both run exactly this, so their
    verdicts cannot drift. Appends (pubkey, sign_bytes, sig) lanes to
    ``items``; returns [(lane_idx, validator_idx)]. Raises
    CommitVerifyError on structural failures."""
    _basic_checks(vals, commit, height, block_id)
    total = vals.total_voting_power()
    lanes = []
    tallied_known = 0
    for i, cs in enumerate(commit.signatures):
        if not cs.for_block():
            continue
        val = vals.get_by_index(i)
        if val.address != cs.validator_address:
            raise CommitVerifyError(f"commit sig {i} address mismatch")
        lanes.append((len(items), i))
        items.append(
            (val.pub_key, _commit_sign_bytes(chain_id, commit, cs), cs.signature)
        )
        tallied_known += val.voting_power
        if not all_signatures and tallied_known * 3 > total * 2:
            break  # enough power collected; verify just these lanes
    return lanes


def _fold_light_lanes(
    lanes: list, oks: list, vals: ValidatorSet, commit: Commit
) -> None:
    """Shared tally/verdict fold for LIGHT verification."""
    tallied = 0
    for lane, i in lanes:
        if not oks[lane]:
            raise ErrInvalidSignature(f"invalid signature for validator {i}")
        if commit.signatures[i].for_block():
            tallied += vals.get_by_index(i).voting_power
    total = vals.total_voting_power()
    if not tallied * 3 > total * 2:
        raise ErrNotEnoughVotingPower(
            f"tallied {tallied} <= 2/3 of {total}"
        )


def _collect_trusting_lanes(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: Fraction,
    all_signatures: bool,
    items: list,
):
    """Shared lane builder for TRUSTING verification (see
    _collect_light_lanes). Returns ([(lane_idx, voting_power)],
    total, need)."""
    if commit is None:
        raise CommitVerifyError("nil commit")
    if trust_level.numerator * 3 < trust_level.denominator or (
        trust_level.numerator > trust_level.denominator
    ):
        raise CommitVerifyError("trust level must be in [1/3, 1]")
    total = vals.total_voting_power()
    need = total * trust_level.numerator
    lanes = []
    seen = set()
    tallied_known = 0
    for cs in commit.signatures:
        if not cs.for_block():
            continue
        idx, val = vals.get_by_address(cs.validator_address)
        if idx < 0:
            continue
        if idx in seen:
            raise CommitVerifyError("double vote from same validator")
        seen.add(idx)
        lanes.append((len(items), val.voting_power))
        items.append(
            (val.pub_key, _commit_sign_bytes(chain_id, commit, cs), cs.signature)
        )
        tallied_known += val.voting_power
        if (
            not all_signatures
            and tallied_known * trust_level.denominator > need
        ):
            break
    return lanes, total, need


def _fold_trusting_lanes(
    lanes: list, oks: list, total, need, trust_level: Fraction
) -> None:
    """Shared tally/verdict fold for TRUSTING verification."""
    tallied = 0
    for lane, power in lanes:
        if not oks[lane]:
            raise ErrInvalidSignature("invalid signature in trusted commit")
        tallied += power
    if not tallied * trust_level.denominator > need:
        raise ErrNotEnoughVotingPower(
            f"trusted tally {tallied} <= {trust_level} of {total}"
        )


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    cache: Optional[SignatureCache] = None,
    all_signatures: bool = False,
    priority: Optional[int] = None,
) -> None:
    """Light verification: only signatures for block_id are checked and
    tallied up to the 2/3 threshold (reference :65; all_signatures=True
    checks every block signature — evidence mode, reference :96)."""
    items: list = []
    lanes = _collect_light_lanes(
        chain_id, vals, block_id, height, commit, all_signatures, items
    )
    oks = _run_batch(items, cache, priority=priority, label="light")
    _fold_light_lanes(lanes, oks, vals, commit)


def verify_commits_coalesced_async(
    chain_id: str,
    jobs,
    cache: Optional[SignatureCache] = None,
    light: bool = True,
    priority: Optional[int] = None,
):
    """Async form of verify_commits_coalesced: enqueues ONE lane batch
    for every job's signatures and returns a handle whose ``result()``
    blocks for the verdicts and yields the per-job error list. The
    blocksync reactor dispatches window K+1 through this before
    applying window K's blocks, hiding the device+link latency behind
    host execution (reference blocksync/reactor.go:560-700 is strictly
    sequential per block)."""
    # the ticket's first stage (docs/TRACE.md "One ticket, one
    # timeline"): entry to submit() returned, on the caller's thread
    with global_tracer().annotated_span(
        "validation.coalesce.build", tid=_TID_CALLER, jobs=len(jobs)
    ) as sp:
        items, plans, errors = _coalesce_lanes(chain_id, jobs, light)
        batch_handle = _run_batch_async(
            items, cache, priority=priority, label="coalesced"
        )
        sp.set(
            ticket=getattr(batch_handle, "ticket_id", None),
            lanes=len(items),
        )
    return _CoalescedHandle(batch_handle, jobs, plans, errors)


class _SeamLanes:
    """The lanes of one batch as ``_plan_commit`` collects them, a
    commit at a time, by columns: ``len()`` = lanes so far."""

    __slots__ = ("msgs", "commits", "key_parts", "sig_parts")

    def __init__(self) -> None:
        self.msgs: list = []  # sign bytes, one a lane
        # a commit: (its set's columns, validators read, their votes)
        self.commits: list = []
        # a commit's key rows and its signatures end to end, while
        # every commit so far could be held by columns; else None
        self.key_parts: Optional[list] = []
        self.sig_parts: list = []

    def __len__(self) -> int:
        return len(self.msgs)

    def add(self, cols, want: list, votes: list, sign_bytes) -> None:
        """One commit's lanes: the validator index and the vote of
        each, in lane order, and their sign bytes."""
        self.msgs.extend(sign_bytes)
        self.commits.append((cols, want, votes))
        if self.key_parts is None or not want:
            return
        # the commit's signatures checked by their joined length (and
        # the longest, so that a 63 and a 65 do not pass as two 64s)
        sigs = [cs.signature for cs in votes]
        joined = b"".join(sigs)
        if (
            cols.key_rows is None
            or len(joined) != 64 * len(sigs)
            or max(map(len, sigs)) != 64
        ):
            # another curve, an odd key, an odd signature: this
            # batch's lanes go as tuples (crypto/scheduler splits
            # them by curve, ops/ed25519 refuses the odd ones)
            self.key_parts = None
            return
        lo, hi = want[0], want[-1] + 1
        self.key_parts.append(
            cols.key_rows[lo:hi]
            if hi - lo == len(want)  # ascending, so one run of the set
            else cols.key_rows[want]
        )
        self.sig_parts.append(joined)

    def batch(self):
        """What ``_run_batch_async`` is handed: a LaneBatch, the key
        rows and signatures concatenated once; or the
        ``(pubkey, sign_bytes, sig)`` tuples where some commit could
        not be held by columns (and ``[]`` for no lane)."""
        if not self.msgs:
            return []
        if self.key_parts is not None:
            return LaneBatch(
                self.msgs,
                np.concatenate(self.key_parts),
                np.frombuffer(b"".join(self.sig_parts), np.uint8).reshape(
                    -1, 64
                ),
            )
        return list(
            zip(
                [cols.pub_keys[i] for cols, want, _ in self.commits for i in want],
                self.msgs,
                [cs.signature for _, _, votes in self.commits for cs in votes],
            )
        )


def _coalesce_lanes(chain_id: str, jobs, light: bool):
    """One lane batch for every job's signatures, planned a COMMIT at
    a time from the columns of its validator set: (items, per-job
    plan, per-job structural error or None). ``items`` is the batch by
    columns, a crypto/lanes.LaneBatch, or, where a set holds another
    curve or an odd key or a commit an odd signature, the
    ``(pubkey, sign_bytes, sig)`` tuples of the same lanes. A plan is
    (first lane, validator index of every lane the job reads, tallied
    power of those that voted for the block); a job that failed reads
    none."""
    lanes = _SeamLanes()
    plans = []
    errors: list = [None] * len(jobs)
    for j, (vals, block_id, height, commit) in enumerate(jobs):
        first = len(lanes)
        try:
            _basic_checks(vals, commit, height, block_id)
            want, tallied = _plan_commit(chain_id, vals, commit, light, lanes)
        except CommitVerifyError as e:
            errors[j] = e
            want, tallied = (), 0
        plans.append((first, want, tallied))
    return lanes.batch(), plans, errors


def _plan_commit(
    chain_id: str, vals: ValidatorSet, commit: Commit, light: bool,
    lanes: _SeamLanes,
):
    """The lanes ONE commit's verification reads, added to ``lanes``
    by columns (its set's columns, the validator index and the vote of
    each lane, the sign bytes of each): what the loops of
    verify_commit (``light=False``: every non-absent vote) and
    _collect_light_lanes (the for-block votes up to the one with which
    the tally passes 2/3) read, in their order, from one pass over the
    commit's flags and slices of the set's columns. Returns (validator
    index of each lane, tallied power of the for-block ones). Raises
    the address mismatch of the first lane that has one, after the
    lanes before it (the loops had appended them)."""
    cols = vals.columns()
    sigs = commit.signatures
    for_block = [cs.block_id_flag == BLOCK_ID_FLAG_COMMIT for cs in sigs]
    if light:
        # the tally after each for-block vote: read up to the first
        # with which it passes 2/3, every one where none does
        tally = list(accumulate(compress(cols.powers, for_block)))
        cut = bisect_right(tally, vals.total_voting_power() * 2 // 3) + 1
        tallied = tally[min(cut, len(tally)) - 1] if tally else 0
        reads = for_block
    else:
        tallied = sum(compress(cols.powers, for_block))
        cut = len(sigs)
        reads = [cs.block_id_flag != BLOCK_ID_FLAG_ABSENT for cs in sigs]

    def read(column) -> list:
        return list(islice(compress(column, reads), cut))

    want = read(range(len(sigs)))
    votes = read(sigs)
    addresses = read(cols.addresses)
    mismatch = None
    if [cs.validator_address for cs in votes] != addresses:
        at = next(
            k for k, cs in enumerate(votes)
            if cs.validator_address != addresses[k]
        )
        mismatch = CommitVerifyError(f"commit sig {want[at]} address mismatch")
        votes = votes[:at]
    # sign bytes once a distinct timestamp (and flag class, where the
    # nil votes are read too)
    stamps = [cs.timestamp_ns for cs in votes]
    if not light:
        stamps = list(zip(read(for_block), stamps))
    sign_bytes = {
        stamp: _commit_sign_bytes(chain_id, commit, cs)
        for stamp, cs in dict(zip(stamps, votes)).items()
    }
    lanes.add(
        cols, want[: len(votes)], votes,
        map(sign_bytes.__getitem__, stamps),
    )
    if mismatch is not None:
        raise mismatch
    return want, tallied


class _CoalescedHandle:
    """``result()`` blocks for the lane verdicts and folds them back
    into per-job errors (tally + 2/3 check per commit)."""

    __slots__ = ("_batch", "_jobs", "_plans", "_errors")

    def __init__(self, batch, jobs, plans, errors) -> None:
        self._batch = batch
        self._jobs = jobs
        self._plans = plans
        self._errors = errors

    def result(self):
        batch = self._batch
        if not isinstance(batch, _BatchHandle):
            # a stand-in for the batch route (tests, the benchmark's
            # control): no ticket, so no stage to record
            return self._fold(_falses(batch.result()))
        verdicts = batch.wait()
        # the ticket's last stage: verdicts in hand to errors returned
        # (cache feed + tally fold), on the caller's/executor's thread
        with global_tracer().annotated_span(
            "validation.coalesce.fold", tid=_TID_CALLER,
            ticket=batch.ticket_id, jobs=len(self._jobs),
            lanes=len(batch._items),
        ):
            return self._fold(batch.refused(verdicts))

    def _fold(self, refused):
        """``refused``: the lanes of the batch with no valid
        signature, ascending. A job's first one names its error; a job
        with none passed every lane it reads, so its tally is the
        plan's."""
        errors, jobs, plans = self._errors, self._jobs, self._plans
        firsts = [first for first, _, _ in plans]
        for lane in refused:
            j = bisect_right(firsts, lane) - 1
            if errors[j] is None:
                first, want, _ = plans[j]
                errors[j] = ErrInvalidSignature(
                    f"invalid signature for validator {want[lane - first]} "
                    f"at height {jobs[j][2]}"
                )
        for j, (vals, _, height, _) in enumerate(jobs):
            if errors[j] is None:
                tallied = plans[j][2]
                if not tallied * 3 > vals.total_voting_power() * 2:
                    errors[j] = ErrNotEnoughVotingPower(
                        f"height {height}: tallied {tallied} <= 2/3"
                    )
        return errors


def verify_commits_coalesced(
    chain_id: str,
    jobs,
    cache: Optional[SignatureCache] = None,
    light: bool = True,
    priority: Optional[int] = None,
) -> list:
    """Verify MANY commits in one TPU dispatch (cross-height coalescing).

    jobs: list of (vals, block_id, height, commit). Returns a list of
    None (success) or CommitVerifyError per job. This is the bulk seam
    the reference cannot express: its batch verifier is per-commit
    (types/validation.go:261); here blocksync/light coalesce whole
    windows of heights into one signature-lane batch (BASELINE.json
    north star: amortize thousands of validator sigs per XLA dispatch).
    """
    return verify_commits_coalesced_async(
        chain_id, jobs, cache=cache, light=light, priority=priority
    ).result()


def verify_commit_jobs_coalesced(
    chain_id: str,
    jobs,
    cache: Optional[SignatureCache] = None,
    priority: Optional[int] = None,
) -> list:
    """Mixed-kind coalesced verification: MANY light and trusting
    commit checks land in ONE lane batch (the light-client serving
    plane's cross-client seam, light/serving.py — a bisection hop is
    one trusting + one light check, and concurrent clients' hops
    coalesce here).

    jobs: list of either
        ("light", vals, block_id, height, commit)
        ("trusting", vals, commit, trust_level)

    Returns one entry per job: None (success) or the exact
    CommitVerifyError subclass the serial path raises —
    serial-equivalence is BY CONSTRUCTION: collection and fold run
    the same _collect_*/_fold_* helpers verify_commit_light and
    verify_commit_light_trusting run, just over one shared lane
    batch (asserted end to end by tests/test_light_serving.py and
    in-bench)."""
    items: list = []
    metas: list = []
    errors: list = [None] * len(jobs)
    for j, job in enumerate(jobs):
        kind = job[0]
        try:
            if kind == "light":
                _, vals, block_id, height, commit = job
                lanes = _collect_light_lanes(
                    chain_id, vals, block_id, height, commit, False,
                    items,
                )
                metas.append(("light", lanes, vals, commit))
            elif kind == "trusting":
                _, vals, commit, trust_level = job
                lanes, total, need = _collect_trusting_lanes(
                    chain_id, vals, commit, trust_level, False, items
                )
                metas.append(
                    ("trusting", lanes, total, need, trust_level)
                )
            else:
                raise CommitVerifyError(f"unknown job kind {kind!r}")
        except CommitVerifyError as e:
            errors[j] = e
            metas.append(None)
    oks = _run_batch(items, cache, priority=priority, label="jobs")
    for j, meta in enumerate(metas):
        if meta is None:
            continue
        try:
            if meta[0] == "light":
                _, lanes, vals, commit = meta
                _fold_light_lanes(lanes, oks, vals, commit)
            else:
                _, lanes, total, need, trust_level = meta
                _fold_trusting_lanes(
                    lanes, oks, total, need, trust_level
                )
        except CommitVerifyError as e:
            errors[j] = e
    return errors


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: Fraction = Fraction(1, 3),
    cache: Optional[SignatureCache] = None,
    all_signatures: bool = False,
    priority: Optional[int] = None,
) -> None:
    """Trusting verification against an *old* validator set: tally power
    of trusted validators who signed; require > trust_level of trusted
    total (reference :148; used by light bisection + evidence)."""
    items: list = []
    lanes, total, need = _collect_trusting_lanes(
        chain_id, vals, commit, trust_level, all_signatures, items
    )
    oks = _run_batch(items, cache, priority=priority, label="trusting")
    _fold_trusting_lanes(lanes, oks, total, need, trust_level)


def verify_extended_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_hash: bytes,
    height: int,
    ec,
    cache: Optional[SignatureCache] = None,
    priority: Optional[int] = None,
) -> None:
    """Full extended-commit verification, shared by every path that
    persists an EC received from a peer (blocksync block responses and
    the consensus catch-up gossip — the analog of the checks guarding
    reference SaveBlockWithExtendedCommit, blocksync/reactor.go:648):

      * the EC binds to this height + block hash;
      * the embedded plain commit fully verifies against ``vals``;
      * non-commit lanes carry no extension data (reference
        ExtendedCommitSig.ValidateBasic — unverifiable attacker bytes
        must never be persisted / reach the app);
      * every commit lane has an extension signature and all of them
        verify in one batch.

    Raises CommitVerifyError on any failure.
    """
    from .canonical import vote_extension_sign_bytes

    if ec.height != height or ec.block_id.hash != block_hash:
        raise CommitVerifyError("extended commit does not bind to block")
    verify_commit(
        chain_id,
        vals,
        ec.block_id,
        height,
        ec.to_commit(),
        cache=cache,
        priority=priority,
    )
    items = []
    for i, s in enumerate(ec.extended_signatures):  # bftlint: disable=ASY117 — verifying an O(V) commit payload is O(V) by construction; runs once per commit-block received and the curve math is batch-verified
        if not s.for_block():
            if s.extension or s.extension_signature:
                raise CommitVerifyError(
                    f"sig {i}: extension data on non-commit lane"
                )
            continue
        if not s.extension_signature:
            raise CommitVerifyError(
                f"commit sig {i} missing extension signature"
            )
        val = vals.get_by_index(i)
        items.append(
            (
                val.pub_key,
                vote_extension_sign_bytes(
                    chain_id, height, ec.round, s.extension
                ),
                s.extension_signature,
            )
        )
    if not all(
        _run_batch(items, cache, priority=priority, label="extension")
    ):
        raise CommitVerifyError("invalid extension signature")
