"""commit-live: single commits into FULL verification, open loop.

A pool of distinct signed heights is made from the seed, as the commit
stream's is: commits of the configuration's validator set over
seed-made block ids, no block bodies. One request is one call of
types/validation.verify_commit (every non-absent signature, live
priority, no signature cache): one scheduler ticket of the set's size.

The loop is OPEN. Requests fall due by a Poisson process (exponential
gaps from the seed, the count in a window fixed at rate x seconds) at
the rate the mix fixes; ``callers`` threads each
take the next request, sleep until it is due and make the call, so a
request due while every caller is busy waits for one. A request's
latency runs from the instant it was due to the return of its call, and
``sent - due`` says how late the generator was. What is due inside the
window and has its verdict inside it counts in the rate; what the
window's end cut is waited for, compared, and counted in no rate.

One commit in so many carries one corrupted signature, the lane drawn
over ALL the set's lanes: about a third fall past the lane with which
light verification stops. After the window the plain reference's
VerifyCommit (benchmark/reference_commit.py) runs over the whole pool
and every verdict of the window is held against it.
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from benchmark import chain, reference, reference_commit
from benchmark.generators.commit_stream import _corrupt, _plain_error
from benchmark.probes import between, say

ROUTE = "crypto.sched.route"


def arrivals(seed: int, window_no: int, rate: float, seconds: float) -> list:
    """When each request of a window falls due, seconds after its
    start: a Poisson process of ``rate`` a second GIVEN its count.
    Exponential gaps from the seed, scaled so that exactly
    ``rate * seconds`` of them (rounded, at least one) fall inside the
    window: the arrivals are as irregular as a Poisson process's, and
    the number offered does not change with the seed, so that the rate
    of verdicts says how the system kept up and not how the dice fell
    (at 25 a second the count alone would spread by 3% of itself). A
    function of (seed, window_no, rate, seconds) and nothing else."""
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng([seed, 5, window_no])
    due = np.cumsum(rng.exponential(1.0, n + 1))
    return (due[:n] * (seconds / due[n])).tolist()


def run_open_loop(
    offsets, call, callers: int, stop_s: float,
    clock=time.perf_counter, sleep=time.sleep,
):
    """Make ``call(i)`` for every offset, each no earlier than
    ``t0 + offsets[i]``, from ``callers`` threads that each block on one
    call at a time. Returns (t0, rows): rows[i] is {"due", "sent",
    "done", "out"} on this clock, or None for a request that was still
    unsent ``stop_s`` after t0 (every caller busy until then). An
    exception of a call is raised here, after the others ended."""
    rows = [None] * len(offsets)
    take = itertools.count()
    errors = []
    t0 = clock()

    def caller():
        while True:
            i = next(take)
            if i >= len(offsets):
                return
            due = t0 + offsets[i]
            wait = due - clock()
            if wait > 0:
                sleep(wait)
            sent = clock()
            if sent - t0 >= stop_s:
                continue  # the window closed before a caller was free
            try:
                out = call(i)
            except Exception as e:  # raised below, on the caller of the loop
                errors.append(e)
                return
            rows[i] = {"due": due, "sent": sent, "done": clock(), "out": out}

    threads = [
        threading.Thread(target=caller, name=f"commit-live-{k}", daemon=True)
        for k in range(callers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return t0, rows


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.config = config
        self.mix = mix
        self.seed = seed
        self.record: dict = {}
        self.results: list = []  # (pool index, (error, index)) a verdict
        self._next = 0  # pool index of the next request, over every window
        self._windows = 0
        self._local = threading.local()
        self._build_pool()

    # --- set-up -----------------------------------------------------------

    def _build_pool(self) -> None:
        import cometbft_tpu.types as T
        from cometbft_tpu.crypto.keys import Ed25519PubKey

        cfg, mix = self.config, self.mix
        n_vals, power = cfg["validators"], cfg["voting_power"]
        self.signers = [
            reference.Signer(s) for s in chain.validator_seeds(self.seed, n_vals)
        ]
        rng = np.random.default_rng([self.seed, 3])
        n = mix["pool_heights"]
        every = mix["corrupt_one_in"]
        kinds = mix["corrupt_kinds"]
        # which commits are corrupted and how: every seed has the same
        # number of each kind
        corrupted = {}
        for k, slot in enumerate(rng.permutation(n // every)):
            h = int(slot) * every + int(rng.integers(0, every))
            corrupted[h] = kinds[k % len(kinds)]
        bad_key = reference.undecodable_key()

        def valset(bad_signer):
            """(set, order): the validator set, or the one in which
            signer ``bad_signer`` holds an undecodable key; order[i] is
            the signer of the set's validator i."""
            keys = [s.public for s in self.signers]
            if bad_signer is not None:
                keys[bad_signer] = bad_key
            vs = T.ValidatorSet([T.Validator(Ed25519PubKey(k), power) for k in keys])
            return vs, [keys.index(v.pub_key.key_bytes) for v in vs.validators]

        self._valset = valset
        self._honest = valset(None)
        self._ids = rng.bytes(64 * n)
        self.pool = []  # (program job, plain commit, plain vals)
        self.expected_bad = {}  # pool index -> (kind, validator index)
        for p in range(n):
            kind = corrupted.get(p)
            # over ALL the lanes: full verification reads every one
            lane = int(rng.integers(0, n_vals))
            self.pool.append(self.signed_height(p, kind, lane))
            if kind is not None:
                self.expected_bad[p] = (kind, self._lane_of(p, kind, lane))

    def _lane_of(self, p: int, kind, lane: int) -> int:
        """The validator index that carries pool height ``p``'s
        corruption: the drawn lane, or where the undecodable key
        sorted."""
        if kind != "bad_key":
            return lane
        _, _, plain_vals = self.pool[p]
        return [k for k, _ in plain_vals].index(reference.undecodable_key())

    def signed_height(self, p: int, kind=None, lane: int = 0, flags=None):
        """Pool height ``p`` signed by the whole set: (program job,
        plain commit, plain vals). ``kind`` corrupts the signature at
        validator index ``lane`` (``bad_key``: signer ``lane`` holds an
        undecodable key instead). ``flags`` {validator index: flag}
        makes single votes nil or absent (the tests; the cell's commits
        are all for the block, the configuration's ``assumed``)."""
        import cometbft_tpu.types as T

        cfg = self.config
        n_vals = cfg["validators"]
        if not 0 <= p < self.mix["pool_heights"]:
            raise IndexError(f"the pool has no height {p}")
        height = p + 1
        vs, order = self._valset(lane) if kind == "bad_key" else self._honest
        block_hash = self._ids[64 * p : 64 * p + 32]
        parts_hash = self._ids[64 * p + 32 : 64 * p + 64]
        ts = chain.genesis_time_ns(self.seed) + height * 1_000_000_000
        plain = {
            "height": height, "round": 0, "block_hash": block_hash,
            "parts_total": 1, "parts_hash": parts_hash, "sigs": [],
        }
        vals = vs.validators
        commit_sigs = []
        for i, s_idx in enumerate(order):
            flag = (flags or {}).get(i, reference.FLAG_COMMIT)
            if flag == reference_commit.FLAG_ABSENT:
                plain["sigs"].append((flag, 0, b""))
                commit_sigs.append(T.CommitSig.absent())
                continue
            signer = self.signers[s_idx]
            msg = reference_commit.sign_bytes(cfg["chain_id"], plain, flag, ts)
            if kind is not None and kind != "bad_key" and i == lane:
                sig = _corrupt(kind, signer, self.signers[(s_idx + 1) % n_vals], msg)
            else:
                sig = signer.sign(msg)
            plain["sigs"].append((flag, ts, sig))
            commit_sigs.append(
                T.CommitSig(
                    block_id_flag=flag, validator_address=vals[i].address,
                    timestamp_ns=ts, signature=sig,
                )
            )
        commit = T.Commit(
            height=height, round=0,
            block_id=T.BlockID(block_hash, T.PartSetHeader(1, parts_hash)),
            signatures=commit_sigs,
        )
        plain_vals = [(v.pub_key.key_bytes, v.voting_power) for v in vals]
        return (vs, commit.block_id, height, commit), plain, plain_vals

    def warm_items(self) -> list:
        """The first sound pool commit's lanes as the kernel dispatch
        takes them: the one shape every request of the cell meets."""
        sound = next(p for p in range(len(self.pool)) if p not in self.expected_bad)
        (vs, _, _, commit), plain, _ = self.pool[sound]
        return [
            (
                reference_commit.sign_bytes(
                    self.config["chain_id"], plain, cs.block_id_flag, cs.timestamp_ns
                ),
                vs.validators[i].pub_key.key_bytes,
                cs.signature,
            )
            for i, cs in enumerate(commit.signatures)
            if not cs.is_absent()
        ]

    # --- one request --------------------------------------------------------

    def verify(self, job):
        """One request: the commit's verdict as (error, validator
        index), from the module attribute so that a wrap is the one
        called."""
        from cometbft_tpu.crypto.scheduler import PRIORITY_LIVE
        from cometbft_tpu.types import validation

        vs, block_id, height, commit = job
        try:
            validation.verify_commit(
                self.config["chain_id"], vs, block_id, height, commit,
                cache=None, priority=PRIORITY_LIVE,
            )
        except validation.CommitVerifyError as e:
            return _plain_error(e)
        return (None, None)

    def _call(self, base: int):
        n = len(self.pool)
        local = self._local

        def call(i):
            p = (base + i) % n
            local.ticket = None
            verdict = self.verify(self.pool[p][0])
            return p, verdict, local.ticket

        return call

    def _run(self, offsets, stop_s):
        """``offsets`` through the callers, the scheduler's ``submit``
        wrapped so that each request learns its ticket's id."""
        from cometbft_tpu.crypto import scheduler as crypto_sched

        sched = crypto_sched.scheduler()
        real = sched.submit
        local = self._local

        def submit(items, *args, **kw):
            ticket = real(items, *args, **kw)
            local.ticket = ticket.id
            return ticket

        sched.submit = submit
        try:
            t0, rows = run_open_loop(
                offsets, self._call(self._next), self.mix["callers"], stop_s
            )
        finally:
            del sched.submit
        self._next = (self._next + len(offsets)) % len(self.pool)
        self._windows += 1
        return t0, rows

    def warm(self, probes) -> None:
        """``warm_requests`` through the same callers at the cell's
        rate: the routing calibration fed, the scheduler's and the
        callers' threads started, the keys expanded."""
        mix = self.mix
        rate, n = mix["rate_commits_per_s"], mix["warm_requests"]
        _, rows = self._run(arrivals(self.seed, 0, rate, n / rate), float("inf"))
        say(f"warm-up: {len(rows)} requests; calibration {_calibration()}")

    # --- the window -------------------------------------------------------

    def window(self, probes, seconds: float) -> None:
        from cometbft_tpu.crypto import scheduler as crypto_sched

        sched = crypto_sched.scheduler()
        sched0 = sched.stats()
        cal0 = _calibration()
        offsets = arrivals(
            self.seed, self._windows, self.mix["rate_commits_per_s"], seconds
        )
        t0, rows = self._run(offsets, seconds)
        t_close = time.perf_counter()
        cut = sched.stats()
        if not sched.drain(timeout=60.0):
            raise RuntimeError("verify scheduler did not drain")
        t_end = t0 + seconds
        routes = _routes()
        requests = []
        for row in rows:
            if row is None:
                continue  # due in the window, never sent: cut
            p, verdict, ticket = row["out"]
            self.results.append((p, verdict))
            requests.append(
                {
                    "due": row["due"], "sent": row["sent"], "done": row["done"],
                    "ticket": ticket, "route": routes.get(ticket),
                    "late": row["done"] >= t_end,
                }
            )
        in_time = [r for r in requests if not r["late"]]
        lanes = sum(1 for s in self.pool[0][1]["sigs"] if s[0] != reference_commit.FLAG_ABSENT)
        self.record = {
            "window_s": seconds,
            "rate_commits_per_s": self.mix["rate_commits_per_s"],
            # due to verdict, of every request that got one
            "batch_s": [r["done"] - r["due"] for r in requests],
            "requests": requests,
            "verdicts": len(in_time),
            "sigs_verdicted": len(in_time) * lanes,
            "attempted": len(offsets),
            "cut_commits": len(offsets) - len(in_time),
            "sched": {
                k: cut[k] - sched0[k]
                for k in ("tickets", "lanes", "device_dispatches", "host_chunks", "degraded")
            },
            "dispatches": between(probes.dispatches, t0, t_close),
            "seam_calls": [],
            "calibration": {"start": cal0, "end": _calibration()},
        }
        by_route: dict = {}
        for r in requests:
            by_route[r["route"]] = by_route.get(r["route"], 0) + 1
        say(
            f"commit-live: {len(offsets)} due in {seconds!r} s, {len(in_time)} "
            f"verdicts inside, {len(requests) - len(in_time)} late, "
            f"{len(offsets) - len(requests)} unsent; routes {by_route}; "
            f"calibration {cal0} -> {self.record['calibration']['end']}"
        )

    # --- what the harness reports ----------------------------------------

    def end_to_end(self) -> dict:
        from benchmark.lookup import percentile

        r = self.record
        lat = sorted(r["batch_s"])
        lag = sorted(q["sent"] - q["due"] for q in r["requests"])
        say(
            f"verify_batch: {len(lat)} commits with a verdict; the 95th "
            f"percentile has {len(lat) - int(0.95 * len(lat))} samples beyond it; "
            f"median {1e3 * percentile(lat, 50)!r} ms, sent - due 99th percentile "
            f"{1e3 * percentile(lag, 99)!r} ms"
        )
        return {
            "verify_rate": r["sigs_verdicted"] / r["window_s"],
            "verify_batch_p95": 1e3 * percentile(lat, 95),
        }

    def counts(self) -> tuple:
        """(attempted, failed): commits due in the window; those with
        no verdict for another reason than the cut (none: a call that
        fails raises)."""
        r = self.record
        return r["attempted"], r["attempted"] - r["verdicts"] - r["cut_commits"]

    # --- correct ----------------------------------------------------------

    def compare(self) -> list:
        verifier = reference.Verifier()
        want = []
        wrong_plan = 0
        n_vals = self.config["validators"]
        for p, (_, plain, plain_vals) in enumerate(self.pool):
            err, idx, lanes = reference_commit.full_verify(
                verifier, self.config["chain_id"], plain_vals, plain
            )
            want.append((err, idx))
            planned = self.expected_bad.get(p)
            wrong_plan += (err, idx) != (
                ("invalid_signature", planned[1]) if planned else (None, None)
            )
            wrong_plan += lanes != n_vals
        differ = sum(got != want[p] for p, got in self.results)
        light = 2 * n_vals // 3 + 1
        say(
            f"compare: verdicts compared={len(self.results)} pool={len(self.pool)} "
            f"reference rejects={sum(1 for w in want if w[0] is not None)} "
            f"(past light verification's lanes: "
            f"{sum(1 for _, i in self.expected_bad.values() if i >= light)}) "
            f"reference slow-path verifies={verifier.slow_path}"
        )
        return [
            ("verdicts_compared_min1", float(len(self.results) < 1), 0.0),
            ("commit_verdicts_differ", float(differ), 0.0),
            ("reference_vs_plan_differ", float(wrong_plan), 0.0),
            ("degraded_dispatches", float(self.record["sched"]["degraded"]), 0.0),
        ]

    def free(self) -> None:
        pass


def _calibration() -> dict:
    """What the router has learned (crypto/batch.calibration)."""
    from cometbft_tpu.crypto import batch as crypto_batch

    cal = crypto_batch.calibration
    return {
        "flat_s": cal.flat_s, "lane_s": cal.lane_s, "host_s": cal.host_s,
        "crossover": cal.crossover(), "device_samples": cal.device_samples,
    }


def _routes() -> dict:
    """{ticket id: "device" | "host"} from the process tracer's
    ``crypto.sched.route`` spans; empty where the ring is off or has
    dropped an event."""
    from cometbft_tpu.trace import global_tracer

    tracer = global_tracer()
    if not tracer.enabled or tracer.stats()["dropped"]:
        return {}
    return {
        e["args"].get("ticket"): e["args"].get("path")
        for e in tracer.snapshot()
        if e["name"] == ROUTE
    }
