"""The verify seam plans and folds a COMMIT at a time from the columns
of its validator set (types/validation._coalesce_lanes / _plan_commit /
_CoalescedHandle._fold): held here to the plain per-signature statement
of the rule, which stays in the single-commit verifiers.

  * the lanes: ``_coalesce_lanes`` hands on, BY COLUMNS (a
    crypto/lanes.LaneBatch: the sign bytes one a lane, key and
    signature rows), the lanes (same keys, same bytes, same order) that
    ``_collect_light_lanes`` appends a commit at a time, and for
    ``light=False`` those of the per-signature loop it replaced (kept
    below, verbatim); and as that loop's own tuples, the set's own key
    objects, where a set holds another curve or an odd key or a commit
    a signature that is not 64 bytes;
  * the verdicts: ``verify_commits_coalesced`` names the error type,
    the validator and the text that ``verify_commit_light`` /
    ``verify_commit`` raise for the same commit under the same lane
    verdicts, with a refused lane at every position class;
  * the cache: ``contains_many`` / ``add_many`` leave what the per-lane
    calls in lane order leave; the seam with a cache submits the lanes
    and leaves the cache that the per-lane seam did;
  * the set's columns follow ``update_with_change_set`` and ``copy()``;
  * no ``Commit`` gains a memo: the speed may not come from a second
    visit (the benchmark's commit stream revisits its pool).

No signature is verified here: the scheduler is a stub that refuses the
lanes whose signature bytes a test names, so both sides of every
comparison see the same verdicts. The real route has its own tests
(test_coalesce.py, test_ticket_timeline.py).
"""

import dataclasses
import random

import numpy as np
import pytest

from cometbft_tpu import types as T
from cometbft_tpu.crypto.keys import (
    Ed25519PrivKey,
    Ed25519PubKey,
    Secp256k1PrivKey,
)
from cometbft_tpu.crypto.lanes import LaneBatch
from cometbft_tpu.types import validation as V
from cometbft_tpu.types.signature_cache import SignatureCache
from cometbft_tpu.types.validator_set import Validator, ValidatorSet

CHAIN_ID = "seam-plan"
COMMIT, NIL, ABSENT = (
    T.BLOCK_ID_FLAG_COMMIT, T.BLOCK_ID_FLAG_NIL, T.BLOCK_ID_FLAG_ABSENT,
)
_KEYS = [Ed25519PrivKey.generate().pub_key() for _ in range(40)]


# --- the stub scheduler ----------------------------------------------------


class _Ticket:
    def __init__(self, id_, oks):
        self.id, self._oks = id_, oks

    def result(self, timeout=None):
        return len(self._oks) > 0 and all(self._oks), self._oks


class _StubScheduler:
    """``submit`` as the seam calls it: records the lanes it is handed,
    tuples or columns, and refuses those whose signature is in
    ``refuse``; a columnar ticket's verdicts are a bool array, as the
    scheduler's are."""

    def __init__(self, refuse=()):
        self.refuse = set(refuse)
        self.submitted = []

    def submit(self, lanes, priority=None, label=""):
        if isinstance(lanes, LaneBatch):
            self.submitted.append(lanes)
            oks = np.array(
                [row.tobytes() not in self.refuse for row in lanes.sigs]
            )
        else:
            assert isinstance(lanes, list)
            self.submitted.append(list(lanes))
            oks = [sig not in self.refuse for _, _, sig in lanes]
        return _Ticket(len(self.submitted), oks)


@pytest.fixture
def stub(monkeypatch):
    sched = _StubScheduler()
    monkeypatch.setattr(V.crypto_sched, "scheduler", lambda: sched)
    return sched


# --- random validator sets and commits -------------------------------------


def _valset(rng, n=None):
    n = n or rng.randint(1, 12)
    keys = rng.sample(_KEYS, n)
    # unequal powers, with ties (the set orders ties by address)
    return ValidatorSet(
        [Validator(k, rng.choice([1, 1, 2, 3, 5, 8, 13, 40])) for k in keys]
    )


def _commit(rng, vs, height, flags=None, distinct_ts=False):
    n = vs.size()
    flags = flags or [COMMIT] * n
    base = 1_700_000_000_000_000_000 + height * 10**9
    sigs = []
    for i, flag in enumerate(flags):
        if flag == ABSENT:
            sigs.append(T.CommitSig.absent())
            continue
        sigs.append(
            T.CommitSig(
                block_id_flag=flag,
                validator_address=vs.validators[i].address,
                timestamp_ns=base + (rng.randrange(10**6) if distinct_ts else 0),
                signature=rng.randbytes(64),
            )
        )
    bid = T.BlockID(rng.randbytes(32), T.PartSetHeader(1, rng.randbytes(32)))
    return bid, T.Commit(height=height, round=0, block_id=bid, signatures=sigs)


def _cut(vs, flags):
    """Index in the set of the for-block vote with which the tally
    passes 2/3 (None where it never does)."""
    total = vs.total_voting_power()
    tallied = 0
    for i, flag in enumerate(flags):
        if flag == COMMIT:
            tallied += vs.validators[i].voting_power
            if tallied * 3 > total * 2:
                return i
    return None


def _with_flags(rng, vs, where, flag):
    """Flags of a commit with one ``flag`` vote before / after the
    light cut-off of the commit that has it (None where the set has
    no such place)."""
    n = vs.size()
    for _ in range(50):
        flags = [COMMIT] * n
        i = rng.randrange(n)
        flags[i] = flag
        cut = _cut(vs, flags)
        if cut is None:
            continue
        if (where == "before") == (i < cut):
            return flags
    return None


def _replace_sig(commit, i, **changes):
    sigs = list(commit.signatures)
    sigs[i] = dataclasses.replace(sigs[i], **changes)
    return dataclasses.replace(commit, signatures=sigs)


def _job(rng, scenario, height):
    """One (vals, block_id, height, commit) of a scenario. Every set
    is fresh, so a call mixes several sets."""
    for _ in range(200):
        vs = _valset(rng, n=rng.randint(4, 12))
        n = vs.size()
        kw = {}
        flags = None
        if scenario.startswith(("absent_", "nil_")):
            kind, where = scenario.split("_")[0], scenario.split("_")[1]
            flags = _with_flags(rng, vs, where, ABSENT if kind == "absent" else NIL)
            if flags is None:
                continue
        elif scenario == "mixed_flags":
            flags = [rng.choice([COMMIT] * 5 + [NIL, ABSENT, 7]) for _ in range(n)]
        elif scenario == "short_of_two_thirds":
            flags = [rng.choice([COMMIT, NIL, ABSENT]) for _ in range(n)]
            if _cut(vs, flags) is not None:
                continue
        elif scenario == "distinct_timestamps":
            kw["distinct_ts"] = True
            flags = [rng.choice([COMMIT] * 6 + [NIL, ABSENT]) for _ in range(n)]
        bid, commit = _commit(rng, vs, height, flags, **kw)
        job = (vs, bid, height, commit)
        if scenario.startswith("mismatch_"):
            cut = _cut(vs, [COMMIT] * n)
            if scenario == "mismatch_before_cut":
                i = rng.randrange(cut + 1)
            elif cut + 1 < n:
                i = rng.randrange(cut + 1, n)
            else:
                continue
            commit = _replace_sig(commit, i, validator_address=rng.randbytes(20))
            job = (vs, bid, height, commit)
        elif scenario == "wrong_size":
            commit = dataclasses.replace(commit, signatures=commit.signatures[:-1])
            job = (vs, bid, height, commit)
        elif scenario == "wrong_height":
            job = (vs, bid, height + 1, commit)
        elif scenario == "wrong_block_id":
            other = T.BlockID(rng.randbytes(32), bid.part_set_header)
            job = (vs, other, height, commit)
        elif scenario == "nil_commit":
            job = (vs, bid, height, None)
        return job
    raise AssertionError(f"no {scenario} commit found")


SCENARIOS = [
    "full",
    "absent_before_cut", "absent_after_cut",
    "nil_before_cut", "nil_after_cut",
    "mixed_flags",
    "mismatch_before_cut", "mismatch_after_cut",
    "short_of_two_thirds",
    "wrong_size", "wrong_height", "wrong_block_id", "nil_commit",
    "distinct_timestamps",
]


def _jobs(rng, scenario, n=6):
    """The scenario's commits among plain ones, several sets a call."""
    jobs = []
    for k in range(n):
        kind = scenario if k % 2 == 0 else rng.choice(["full", "mixed_flags"])
        jobs.append(_job(rng, kind, height=10 + k))
    return jobs


# --- the per-signature statement of the rule -------------------------------


def _per_signature_lanes(chain_id, jobs, light):
    """The loop ``_coalesce_lanes`` ran a signature at a time before
    it planned by columns, verbatim: (items, per job [(lane, validator
    index)], per job structural error or None)."""
    items, job_lanes, errors = [], [], [None] * len(jobs)
    for j, (vals, block_id, height, commit) in enumerate(jobs):
        lanes = []
        try:
            V._basic_checks(vals, commit, height, block_id)
            total = vals.total_voting_power()
            tallied_known = 0
            for i, cs in enumerate(commit.signatures):
                want = cs.for_block() if light else not cs.is_absent()
                if not want:
                    continue
                val = vals.get_by_index(i)
                if val.address != cs.validator_address:
                    raise V.CommitVerifyError(
                        f"commit sig {i} address mismatch"
                    )
                lanes.append((len(items), i))
                items.append((
                    val.pub_key,
                    V._commit_sign_bytes(chain_id, commit, cs),
                    cs.signature,
                ))
                if light and cs.for_block():
                    tallied_known += val.voting_power
                    if tallied_known * 3 > total * 2:
                        break
        except V.CommitVerifyError as e:
            errors[j] = e
            lanes = []
        job_lanes.append(lanes)
    return items, job_lanes, errors


def _per_signature_fold(jobs, job_lanes, errors, oks):
    """The fold that went with it, verbatim."""
    errors = list(errors)
    for j, (vals, block_id, height, commit) in enumerate(jobs):
        if errors[j] is not None:
            continue
        tallied = 0
        bad = None
        for lane, i in job_lanes[j]:
            if not oks[lane]:
                bad = V.ErrInvalidSignature(
                    f"invalid signature for validator {i} "
                    f"at height {height}"
                )
                break
            if commit.signatures[i].for_block():
                tallied += vals.get_by_index(i).voting_power
        if bad is not None:
            errors[j] = bad
        elif not tallied * 3 > vals.total_voting_power() * 2:
            errors[j] = V.ErrNotEnoughVotingPower(
                f"height {height}: tallied {tallied} <= 2/3"
            )
    return errors


def _same_items(got, want, form=None):
    """``got``, columns or tuples (``form``: which it has to be),
    holds the lanes ``want``, the per-signature tuples."""
    assert len(got) == len(want)
    if form is not None:
        assert isinstance(got, LaneBatch) == (form == "columns")
    if isinstance(got, LaneBatch):
        assert got.msgs == [sb for _, sb, _ in want]
        assert all(type(sb) is bytes for sb in got.msgs)
        for rows, width, field in (
            (got.keys, 32, [pk.key_bytes for pk, _, _ in want]),
            (got.sigs, 64, [sig for _, _, sig in want]),
        ):
            assert type(rows) is np.ndarray and rows.dtype == np.uint8
            assert rows.shape == (len(want), width)
            assert rows.tobytes() == b"".join(field)
        assert len(got.bad) == 0  # none refused, by construction
        return
    for (pk_a, sb_a, sig_a), (pk_b, sb_b, sig_b) in zip(got, want):
        assert pk_a is pk_b  # the set's own key object
        assert (sb_a, sig_a) == (sb_b, sig_b)
        assert type(sb_a) is bytes and type(sig_a) is bytes


def _fresh(jobs):
    """The same jobs on new Commit objects: no sign-bytes memo."""
    return [
        (vs, bid, h, None if c is None else dataclasses.replace(c))
        for vs, bid, h, c in jobs
    ]


def _same_error(got, want):
    assert type(got) is type(want)
    assert str(got) == str(want)


# --- the lanes -------------------------------------------------------------


@pytest.mark.parametrize("light", [True, False], ids=["light", "full"])
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_planned_lanes_are_the_per_signature_lanes(scenario, light):
    for seed in range(8):
        rng = random.Random(f"{scenario}-{seed}")
        jobs = _jobs(rng, scenario)
        items, plans, errors = V._coalesce_lanes(CHAIN_ID, _fresh(jobs), light)
        ref_items, ref_lanes, ref_errors = _per_signature_lanes(
            CHAIN_ID, _fresh(jobs), light
        )
        _same_items(items, ref_items, "columns" if ref_items else "tuples")
        for j, (first, want, _) in enumerate(plans):
            got = [(first + k, i) for k, i in enumerate(want)]
            assert got == ref_lanes[j], (seed, j)
            if ref_errors[j] is None:
                assert errors[j] is None
            else:
                _same_error(errors[j], ref_errors[j])
        if not light:
            continue
        # and a commit at a time through the single-commit builder
        one_by_one = []
        for vs, bid, height, commit in _fresh(jobs):
            try:
                V._collect_light_lanes(
                    CHAIN_ID, vs, bid, height, commit, False, one_by_one
                )
            except V.CommitVerifyError:
                pass
        _same_items(items, one_by_one)


def _odd_jobs(rng, odd):
    """Plain commits of several sets, one of them with the odd thing:
    (jobs, signatures the odd commit's verification refuses by form)."""
    jobs = [_job(rng, "mixed_flags", height=20 + k) for k in range(5)]
    height = 22
    # equal powers: light verification reads the first five of seven
    keys = rng.sample(_KEYS, 7)
    if odd == "secp256k1_key":
        keys[3] = Secp256k1PrivKey.generate().pub_key()
    elif odd == "key_31_bytes":
        keys[3] = Ed25519PubKey(rng.randbytes(31))
    vs = ValidatorSet([Validator(k, 10) for k in keys])
    bid, commit = _commit(rng, vs, height)
    sizes = {
        "signature_63_bytes": {1: 63},
        "signatures_63_and_65_bytes": {0: 63, 1: 65},
        "signature_empty": {0: 0},
    }.get(odd, {})
    for i, size in sizes.items():
        commit = _replace_sig(commit, i, signature=rng.randbytes(size))
    jobs[2] = (vs, bid, height, commit)
    return jobs


@pytest.mark.parametrize("light", [True, False], ids=["light", "full"])
@pytest.mark.parametrize(
    "odd",
    ["signature_63_bytes", "signatures_63_and_65_bytes", "signature_empty",
     "secp256k1_key", "key_31_bytes"],
)
def test_odd_input_takes_the_tuple_form(odd, light, monkeypatch):
    """What the columns cannot hold goes as the per-signature tuples,
    the whole batch of it, chosen by what the input IS; the errors are
    the single-commit verifiers' under the same lane verdicts."""
    for seed in range(4):
        rng = random.Random(f"{odd}-{seed}")
        jobs = _odd_jobs(rng, odd)
        items, plans, errors = V._coalesce_lanes(CHAIN_ID, _fresh(jobs), light)
        ref_items, ref_lanes, _ = _per_signature_lanes(
            CHAIN_ID, _fresh(jobs), light
        )
        _same_items(items, ref_items, "tuples")
        for j, (first, want, _) in enumerate(plans):
            assert [(first + k, i) for k, i in enumerate(want)] == ref_lanes[j]
        # the plain commits alone are held by columns
        plain, _, _ = V._coalesce_lanes(
            CHAIN_ID, _fresh(jobs[:2] + jobs[3:]), light
        )
        assert isinstance(plain, LaneBatch)
        # a lane of the wrong size is refused, as the device path does
        refuse = {
            sig for pk, _, sig in ref_items
            if len(sig) != 64 or len(pk.key_bytes) not in (32, 33)
        } | _refusals(rng, jobs[:2], light, "last")
        want = [_single(CHAIN_ID, job, light, refuse, monkeypatch) for job in jobs]
        sched = _StubScheduler(refuse)
        monkeypatch.setattr(V.crypto_sched, "scheduler", lambda: sched)
        got = V.verify_commits_coalesced(CHAIN_ID, _fresh(jobs), light=light)
        for (_, _, height, _), g, w in zip(jobs, got, want):
            if w is None:
                assert g is None, str(g)
            else:
                assert type(g) is type(w)
                assert str(g) == _coalesced_text(w, height)
        assert len(sched.submitted) == 1
        _same_items(sched.submitted[0], ref_items, "tuples")


def test_a_run_of_the_set_is_a_slice_and_a_gap_a_take(stub):
    """The key rows of a commit's lanes: a view of the set's array
    where the lanes are one run of the set, a take where votes are
    missing between them; either way the rows of the validators read."""
    rng = random.Random(12)
    vs = ValidatorSet([Validator(k, 10) for k in _KEYS[:10]])
    rows = vs.columns().key_rows
    for flags in ([COMMIT] * 10, [ABSENT, NIL] + [COMMIT] * 8,
                  [COMMIT, ABSENT, COMMIT, NIL] + [COMMIT] * 6):
        bid, commit = _commit(rng, vs, 3, flags)
        lanes = V._SeamLanes()
        want, _ = V._plan_commit(CHAIN_ID, vs, commit, True, lanes)
        (part,) = lanes.key_parts
        assert np.array_equal(part, rows[want])
        run = want == list(range(want[0], want[-1] + 1))
        assert (part.base is not None) == run


def test_scenarios_hold_what_they_say():
    """The generator's commits are the cases their names promise."""
    rng = random.Random(5)
    seen = {"before": 0, "after": 0}
    for scenario in ("absent_before_cut", "nil_after_cut"):
        for _ in range(20):
            vs, _, _, commit = _job(rng, scenario, 3)
            flags = [cs.block_id_flag for cs in commit.signatures]
            odd = next(i for i, f in enumerate(flags) if f != COMMIT)
            where = scenario.split("_")[1]
            assert (odd < _cut(vs, flags)) == (where == "before")
            seen[where] += 1
    assert seen == {"before": 20, "after": 20}
    for _ in range(20):
        vs, _, _, commit = _job(rng, "short_of_two_thirds", 3)
        assert _cut(vs, [cs.block_id_flag for cs in commit.signatures]) is None
        vs, _, _, commit = _job(rng, "distinct_timestamps", 3)
        stamps = {cs.timestamp_ns for cs in commit.signatures if cs.signature}
        assert len(stamps) > 1
    powers = {v.voting_power for v in _valset(random.Random(1), 12).validators}
    assert len(powers) > 1  # unequal powers


# --- the verdicts ----------------------------------------------------------


def _single(chain_id, job, light, refuse, monkeypatch):
    """What the single-commit verifier raises for one job under the
    same lane verdicts (None where it passes)."""
    vs, bid, height, commit = job
    sched = _StubScheduler(refuse)
    monkeypatch.setattr(V.crypto_sched, "scheduler", lambda: sched)
    verify = V.verify_commit_light if light else V.verify_commit
    try:
        verify(chain_id, vs, bid, height, commit)
    except V.CommitVerifyError as e:
        return e
    return None


def _coalesced_text(err, height):
    """The text the coalesced seam gives the single-commit error."""
    text = str(err)
    if isinstance(err, V.ErrInvalidSignature):
        return f"{text} at height {height}"
    if isinstance(err, V.ErrNotEnoughVotingPower):
        tallied = text.split()[1]
        return f"height {height}: tallied {tallied} <= 2/3"
    # verify_commit words the mismatch a little longer
    return text.removesuffix(" with validator set")


def _refusals(rng, jobs, light, position):
    """Signatures to refuse: in every job that reads a lane, the
    first / the last lane it reads, or a vote past what it reads."""
    refuse = set()
    _, ref_lanes, _ = _per_signature_lanes(CHAIN_ID, _fresh(jobs), light)
    for (vs, bid, height, commit), lanes in zip(jobs, ref_lanes):
        if commit is None or not lanes:
            continue
        read = [i for _, i in lanes]
        if position == "first":
            refuse.add(commit.signatures[read[0]].signature)
        elif position == "last":
            refuse.add(commit.signatures[read[-1]].signature)
        elif position == "two":
            refuse.add(commit.signatures[read[-1]].signature)
            refuse.add(commit.signatures[rng.choice(read)].signature)
        elif position == "past":
            past = [
                cs.signature for i, cs in enumerate(commit.signatures)
                if i > read[-1] and cs.signature
            ]
            refuse.update(past[:1])
    return refuse


@pytest.mark.parametrize(
    "position", ["none", "first", "last", "two", "past"]
)
@pytest.mark.parametrize("light", [True, False], ids=["light", "full"])
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_coalesced_errors_are_the_single_commit_errors(
    scenario, light, position, monkeypatch
):
    named = 0
    for seed in range(4):
        rng = random.Random(f"{scenario}-{position}-{seed}")
        jobs = _jobs(rng, scenario)
        refuse = _refusals(rng, jobs, light, position)
        want = [_single(CHAIN_ID, job, light, refuse, monkeypatch) for job in jobs]
        sched = _StubScheduler(refuse)
        monkeypatch.setattr(V.crypto_sched, "scheduler", lambda: sched)
        got = V.verify_commits_coalesced(CHAIN_ID, _fresh(jobs), light=light)
        assert len(got) == len(jobs)
        for (vs, bid, height, commit), g, w in zip(jobs, got, want):
            if w is None:
                assert g is None, str(g)
                continue
            assert type(g) is type(w), (str(g), str(w))
            assert str(g) == _coalesced_text(w, height)
            named += isinstance(g, V.ErrInvalidSignature)
        # one ticket, and the list it was handed is the per-signature one
        ref_items, ref_lanes, ref_errors = _per_signature_lanes(
            CHAIN_ID, _fresh(jobs), light
        )
        oks = [sig not in refuse for _, _, sig in ref_items]
        for g, w in zip(
            got, _per_signature_fold(jobs, ref_lanes, ref_errors, oks)
        ):
            assert (g is None and w is None) or _same_error(g, w) is None
        if ref_items:
            assert len(sched.submitted) == 1
            _same_items(sched.submitted[0], ref_items)
        else:
            assert sched.submitted == []
    if position in ("first", "last", "two") and scenario not in (
        "wrong_size", "wrong_height", "wrong_block_id", "nil_commit",
    ):
        assert named  # the case did refuse a lane a job reads


def test_refused_lane_names_its_validator_not_its_lane(stub):
    """Absent votes shift lanes against validator indices: the error
    names the validator, as the benchmark parses it."""
    rng = random.Random(11)
    vs = ValidatorSet([Validator(k, 10) for k in _KEYS[:9]])
    flags = [ABSENT, COMMIT, ABSENT] + [COMMIT] * 6
    bid, commit = _commit(rng, vs, 7, flags)
    cut = _cut(vs, flags)
    assert cut == 8  # the seventh of nine equal votes, on lane 6
    stub.refuse = {commit.signatures[cut].signature}
    (err,) = V.verify_commits_coalesced(CHAIN_ID, [(vs, bid, 7, commit)])
    assert isinstance(err, V.ErrInvalidSignature)
    assert str(err) == f"invalid signature for validator {cut} at height 7"


def test_result_is_repeatable(stub):
    rng = random.Random(3)
    jobs = _jobs(rng, "mixed_flags")
    stub.refuse = _refusals(rng, jobs, True, "first")
    handle = V.verify_commits_coalesced_async(CHAIN_ID, jobs)
    first = [str(e) for e in handle.result()]
    assert [str(e) for e in handle.result()] == first


# --- the cache -------------------------------------------------------------


def _key_batches(rng, n_batches, size_range, universe):
    return [
        [
            SignatureCache.key(b"sb%d" % k, b"sig%d" % k, b"pk%d" % k)
            for k in (
                rng.randrange(universe)
                for _ in range(rng.randint(*size_range))
            )
        ]
        for _ in range(n_batches)
    ]


def _cache_state(cache):
    return list(cache._od), len(cache), cache.hits, cache.misses


@pytest.mark.parametrize(
    "size,batch,universe",
    [(8, (0, 6), 12), (8, (9, 30), 40), (5, (1, 5), 5), (1, (0, 4), 3),
     (64, (10, 40), 50), (0, (1, 4), 3)],
    ids=["small", "batch-larger-than-cache", "all-fit", "size-one",
         "repeats", "size-zero"],
)
def test_bulk_cache_calls_leave_what_per_lane_calls_leave(
    size, batch, universe
):
    for seed in range(10):
        rng = random.Random(seed)
        one, bulk = SignatureCache(size), SignatureCache(size)
        for keys in _key_batches(rng, 12, batch, universe):
            if rng.random() < 0.5:
                want = [one.contains(*k) for k in keys]
                assert bulk.contains_many(keys) == want
            else:
                for k in keys:
                    one.add(*k)
                bulk.add_many(keys)
            assert _cache_state(bulk) == _cache_state(one)
            assert len(bulk) <= max(size, 0)


def _per_lane_seam(items, cache, sched):
    """``_run_batch_async`` + ``fill`` as they ran a lane at a time:
    the verdict of every item, the cache asked and fed in lane order."""
    skip = [cache.contains(sb, sig, pk.key_bytes) for pk, sb, sig in items]
    to_verify = [i for i, s in enumerate(skip) if not s]
    oks = [True] * len(items)
    if to_verify:
        _, verdicts = sched.submit([items[i] for i in to_verify]).result()
        for i, ok in zip(to_verify, verdicts):
            oks[i] = ok
            if ok:
                pk, sb, sig = items[i]
                cache.add(sb, sig, pk.key_bytes)
    return oks


@pytest.mark.parametrize("size", [10_000, 40, 7], ids=["roomy", "tight", "tiny"])
@pytest.mark.parametrize("light", [True, False], ids=["light", "full"])
def test_seam_with_a_cache_submits_and_leaves_what_per_lane_calls_did(
    light, size, monkeypatch
):
    for seed in range(4):
        rng = random.Random(seed)
        pool = _jobs(rng, "mixed_flags", n=10)
        refuse = _refusals(rng, pool, light, "last")
        cache, ref_cache = SignatureCache(size), SignatureCache(size)
        sched, ref_sched = _StubScheduler(refuse), _StubScheduler(refuse)
        monkeypatch.setattr(V.crypto_sched, "scheduler", lambda: sched)
        # overlapping windows: later ones hit what earlier ones fed
        for lo, hi in ((0, 5), (3, 8), (0, 10), (6, 10)):
            jobs = pool[lo:hi]
            got = V.verify_commits_coalesced(
                CHAIN_ID, jobs, cache=cache, light=light
            )
            items, ref_lanes, ref_errors = _per_signature_lanes(
                CHAIN_ID, jobs, light
            )
            oks = _per_lane_seam(items, ref_cache, ref_sched)
            want = _per_signature_fold(jobs, ref_lanes, ref_errors, oks)
            for g, w in zip(got, want):
                if w is None:
                    assert g is None, str(g)
                else:
                    _same_error(g, w)
            assert _cache_state(cache) == _cache_state(ref_cache)
        assert len(sched.submitted) == len(ref_sched.submitted)
        for a, b in zip(sched.submitted, ref_sched.submitted):
            _same_items(a, b, "columns")
        if size == 10_000:
            assert cache.hits > 0  # the windows did overlap


def test_single_commit_paths_fill_over_cache_hits(stub):
    """``_BatchHandle.fill`` (the single-commit verifiers' route):
    a verdict an item, cache hits valid, refused lanes where they
    were, accepted ones fed."""
    rng = random.Random(2)
    vs = _valset(rng, 8)
    bid, commit = _commit(rng, vs, 5)
    items = []
    V._collect_light_lanes(CHAIN_ID, vs, bid, 5, commit, True, items)
    cache = SignatureCache()
    pk, sb, sig = items[2]
    cache.add(sb, sig, pk.key_bytes)
    stub.refuse = {items[1][2], items[5][2]}
    oks = V._run_batch(items, cache)
    assert oks == [i not in (1, 5) for i in range(8)]
    assert stub.submitted == [items[:2] + items[3:]]
    assert len(cache) == 6 and (cache.hits, cache.misses) == (1, 7)
    # every lane cached: no ticket at all
    stub.refuse = set()
    good = [it for i, it in enumerate(items) if i not in (1, 5)]
    handle = V._run_batch_async(good, cache)
    assert handle.ticket_id is None and handle.result() == [True] * 6
    assert len(stub.submitted) == 1


# --- the set's columns -----------------------------------------------------


def _columns_from_scratch(vs):
    return (
        [v.address for v in vs.validators],
        [v.pub_key for v in vs.validators],
        [v.voting_power for v in vs.validators],
    )


def _lists(cols):
    """The columns that are lists; ``key_rows`` is held to them."""
    rows = cols.key_rows
    assert rows.shape == (len(cols.pub_keys), 32) and rows.dtype == np.uint8
    assert not rows.flags.writeable  # shared by every copy of the set
    assert [r.tobytes() for r in rows] == [pk.key_bytes for pk in cols.pub_keys]
    return cols.addresses, cols.pub_keys, cols.powers


def test_columns_are_the_set_in_order_and_memoised():
    vs = _valset(random.Random(4), 10)
    cols = vs.columns()
    assert _lists(cols) == _columns_from_scratch(vs)
    assert sum(cols.powers) == vs.total_voting_power()
    assert vs.columns() is cols


@pytest.mark.parametrize("odd", ["secp256k1", "ed25519_31_bytes"])
def test_a_set_with_an_odd_key_has_no_key_rows(odd):
    key = (
        Secp256k1PrivKey.generate().pub_key()
        if odd == "secp256k1"
        else Ed25519PubKey(b"k" * 31)
    )
    vs = ValidatorSet([Validator(k, 5) for k in _KEYS[:4]] + [Validator(key, 5)])
    cols = vs.columns()
    assert cols.key_rows is None
    assert tuple(cols)[:3] == _columns_from_scratch(vs)
    # and gets them back when the odd key leaves
    vs.update_with_change_set([Validator(key, 0)])
    assert _lists(vs.columns()) == _columns_from_scratch(vs)


@pytest.mark.parametrize("change", ["power", "remove", "add", "key"])
def test_columns_follow_update_with_change_set(change):
    rng = random.Random(change)
    vs = _valset(rng, 8)
    before = vs.columns()
    victim = vs.validators[rng.randrange(8)]
    if change == "power":
        delta = [Validator(victim.pub_key, victim.voting_power + 100)]
    elif change == "remove":
        delta = [Validator(victim.pub_key, 0)]
    elif change == "add":
        fresh = next(k for k in _KEYS if not vs.has_address(k.address()))
        delta = [Validator(fresh, 9)]
    else:
        # a new key under an old address (update keeps the address)
        fresh = next(k for k in _KEYS if not vs.has_address(k.address()))
        delta = [Validator(fresh, victim.voting_power, address=victim.address)]
    vs.update_with_change_set(delta)
    cols = vs.columns()
    assert cols is not before
    assert _lists(cols) == _columns_from_scratch(vs)
    assert _lists(cols) != _lists(before)


def test_columns_on_a_copy(stub):
    vs = _valset(random.Random(6), 8)
    bare = vs.copy()  # copied before the memo exists
    cols = vs.columns()
    twin = vs.copy()
    assert _lists(twin.columns()) == _lists(cols) == _lists(bare.columns())
    # a copy that moves on does not take the original with it
    twin.update_with_change_set([Validator(twin.validators[0].pub_key, 0)])
    assert twin.size() == 7 and len(twin.columns().addresses) == 7
    assert vs.columns() is cols and len(cols.addresses) == 8
    # rotation changes priorities only: the columns stand
    turned = vs.copy_increment_proposer_priority(3)
    assert _lists(turned.columns()) == _lists(cols)
    # and the seam plans the moved-on copy from ITS columns
    rng = random.Random(8)
    bid, commit = _commit(rng, twin, 4)
    assert V.verify_commits_coalesced(CHAIN_ID, [(twin, bid, 4, commit)]) == [None]
    _same_items(
        stub.submitted[-1],
        _per_signature_lanes(CHAIN_ID, [(twin, bid, 4, commit)], True)[0],
        "columns",
    )


# --- the guard against the flattering memo ---------------------------------


@pytest.mark.parametrize("cached", [False, True], ids=["no-cache", "cache"])
@pytest.mark.parametrize("light", [True, False], ids=["light", "full"])
def test_no_commit_gains_a_memo(light, cached, stub):
    """A node that catches up sees a commit once: whatever the seam
    saves it has to save on the first visit. After a build and a fold
    a Commit carries its fields and the sign-bytes memo it always
    carried, nothing else."""
    rng = random.Random(9)
    jobs = _fresh(_jobs(rng, "mixed_flags", n=8))
    stub.refuse = _refusals(rng, jobs, light, "first")
    fields = {f.name for f in dataclasses.fields(T.Commit)}
    cache = SignatureCache() if cached else None
    for _ in range(2):
        V.verify_commits_coalesced(CHAIN_ID, jobs, cache=cache, light=light)
    for _, _, _, commit in jobs:
        assert set(vars(commit)) - fields <= {"_sb_parts"}
        for cs in commit.signatures:
            assert set(vars(cs)) == {f.name for f in dataclasses.fields(cs)}
