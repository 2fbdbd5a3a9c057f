"""commit-stream: signed commits straight into the verify seam.

A pool of distinct signed heights is made from the seed: commits of the
configuration's validator set over seed-made block ids, no block
bodies. Batches of consecutive heights go into
types/validation.verify_commits_coalesced_async (light, catch-up
priority, no signature cache), a closed loop with a fixed number of
batches in flight, cycling the pool. One commit in so many carries one
corrupted signature among the lanes light verification reads, so a
kernel or a route that accepts everything fails on the timed path.

The commits are signed here (benchmark/reference.Signer over the
reference's own sign-bytes); the program gets the commits only. After
the window the plain reference's VerifyCommitLight runs over the whole
pool, and every verdict the seam gave in the window is held against it.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import chain, reference
from benchmark.probes import annotation, between, say

KINDS = ("sig_r_byte", "sig_s_byte", "wrong_key", "bad_key", "s_plus_L")


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.config = config
        self.mix = mix
        self.seed = seed
        self.record: dict = {}
        self.results: list = []  # (first pool height, [(error, index)]) a batch
        self.late: list = []  # the same, of the batches the window's end cut
        self._build_pool()

    # --- set-up -----------------------------------------------------------

    def _build_pool(self) -> None:
        import cometbft_tpu.types as T
        from cometbft_tpu.crypto.keys import Ed25519PubKey

        cfg, mix = self.config, self.mix
        n_vals, power = cfg["validators"], cfg["voting_power"]
        signers = [reference.Signer(s) for s in chain.validator_seeds(self.seed, n_vals)]
        rng = np.random.default_rng([self.seed, 3])
        n = mix["pool_heights"]
        every = mix["corrupt_one_in"]
        kinds = mix["corrupt_kinds"]
        # which commits are corrupted, how, and at which lane: drawn so
        # that every seed has the same number of each kind
        corrupted = {}
        slots = rng.permutation(n // every)
        for k, slot in enumerate(slots):
            h = int(slot) * every + int(rng.integers(0, every))
            corrupted[h] = kinds[k % len(kinds)]

        bad_key = reference.undecodable_key()

        def valset(bad_signer):
            """(set, order): the validator set, or the one in which
            signer ``bad_signer`` holds an undecodable key; order[i] is
            the signer of the set's validator i."""
            keys = [s.public for s in signers]
            if bad_signer is not None:
                keys[bad_signer] = bad_key
            vs = T.ValidatorSet(
                [T.Validator(Ed25519PubKey(k), power) for k in keys]
            )
            return vs, [keys.index(v.pub_key.key_bytes) for v in vs.validators]

        honest = valset(None)
        light_lanes = (2 * n_vals * power) // (3 * power) + 1  # > 2/3
        t0 = chain.genesis_time_ns(self.seed)
        self.pool = []  # (program job, plain commit, plain vals)
        self.expected_bad = {}  # pool index -> (kind, validator index)
        ids = rng.bytes(64 * n)
        for p in range(n):
            height = p + 1
            kind = corrupted.get(p)
            lane = int(rng.integers(0, light_lanes))
            if kind == "bad_key":
                bad_signer = int(rng.integers(0, n_vals))
                vs, order = valset(bad_signer)
                lane = order.index(bad_signer)
                if lane >= light_lanes:
                    raise RuntimeError("the undecodable key sorts past +2/3")
            else:
                vs, order = honest
            block_hash = ids[64 * p : 64 * p + 32]
            parts_hash = ids[64 * p + 32 : 64 * p + 64]
            ts = t0 + height * 1_000_000_000
            msg = reference.vote_sign_bytes(
                cfg["chain_id"], height, 0, block_hash, 1, parts_hash, ts
            )
            sigs = []
            for i, s_idx in enumerate(order):
                signer = signers[s_idx]
                if kind is not None and i == lane:
                    sig = _corrupt(kind, signer, signers[(s_idx + 1) % n_vals], msg)
                else:
                    sig = signer.sign(msg)
                sigs.append(sig)
            if kind is not None:
                self.expected_bad[p] = (kind, lane)
            vals = vs.validators
            commit = T.Commit(
                height=height,
                round=0,
                block_id=T.BlockID(block_hash, T.PartSetHeader(1, parts_hash)),
                signatures=[
                    T.CommitSig(
                        block_id_flag=T.BLOCK_ID_FLAG_COMMIT,
                        validator_address=vals[i].address,
                        timestamp_ns=ts,
                        signature=sig,
                    )
                    for i, sig in enumerate(sigs)
                ],
            )
            plain = {
                "height": height, "round": 0, "block_hash": block_hash,
                "parts_total": 1, "parts_hash": parts_hash,
                "sigs": [(reference.FLAG_COMMIT, ts, sig) for sig in sigs],
            }
            plain_vals = [(v.pub_key.key_bytes, v.voting_power) for v in vals]
            self.pool.append(((vs, commit.block_id, height, commit), plain, plain_vals))
        self.light_lanes = light_lanes

    def warm_items(self) -> list:
        vs, _, _, commit = self.pool[0][0]
        plain = self.pool[0][1]
        msg = reference.vote_sign_bytes(
            self.config["chain_id"], plain["height"], 0, plain["block_hash"],
            1, plain["parts_hash"], plain["sigs"][0][1],
        )
        return [
            (msg, vs.validators[i].pub_key.key_bytes, cs.signature)
            for i, cs in enumerate(commit.signatures)
        ]

    def _batches(self):
        size = self.mix["batch_commits"]
        n = len(self.pool)
        start = 0
        while True:
            yield start, [self.pool[(start + k) % n][0] for k in range(size)]
            start = (start + size) % n

    def _submit(self, jobs):
        from cometbft_tpu.crypto.scheduler import PRIORITY_CATCHUP
        from cometbft_tpu.types import validation

        # the module attribute, so that the probes' wrap is the one called
        return validation.verify_commits_coalesced_async(
            self.config["chain_id"], jobs, cache=None, light=True,
            priority=PRIORITY_CATCHUP,
        )

    def warm(self, probes) -> None:
        """Through the seam as the window will go: the routing
        calibration fed, the scheduler's threads started."""
        batches = self._batches()
        for _ in range(self.mix["warm_batches"]):
            _, jobs = next(batches)
            self._submit(jobs).result()

    # --- the window -------------------------------------------------------

    def window(self, probes, seconds: float) -> None:
        from cometbft_tpu.crypto import scheduler as crypto_sched

        sched = crypto_sched.scheduler()
        sched0 = sched.stats()
        batches = self._batches()
        depth = self.mix["in_flight"]
        in_flight = []  # (start, t_submit, commits, handle)
        rows = []
        t0 = time.perf_counter()
        t_end = t0 + seconds

        def submit():
            start, jobs = next(batches)
            t = time.perf_counter()
            in_flight.append((start, t, len(jobs), self._submit(jobs)))

        for _ in range(depth):
            submit()
        while True:
            start, t_sub, commits, handle = in_flight.pop(0)
            with annotation("bench.verdict_wait"):
                errors = handle.result()
            t_done = time.perf_counter()
            if t_done >= t_end:
                in_flight.insert(0, (start, t_sub, commits, handle))
                break
            rows.append(
                {"t": t_sub, "done": t_done, "commits": commits, "verdicts": len(errors)}
            )
            self.results.append((start, [_plain_error(e) for e in errors]))
            submit()
        t_close = time.perf_counter()
        cut = sched.stats()
        # the cut: wait them out; late is not wrong, so their verdicts
        # are compared too, and counted in no rate
        self.late += [(in_flight[0][0], [_plain_error(e) for e in errors])] + [
            (start, [_plain_error(e) for e in handle.result()])
            for start, _, _, handle in in_flight[1:]
        ]
        if not sched.drain(timeout=60.0):
            raise RuntimeError("verify scheduler did not drain")
        offered = len(rows) * self.mix["batch_commits"] + sum(
            c for _, _, c, _ in in_flight
        )
        verdicts = sum(r["verdicts"] for r in rows)
        self.record = {
            "window_s": seconds,
            "batch_s": [r["done"] - r["t"] for r in rows],
            "verdicts": verdicts,
            "sigs_verdicted": verdicts * self.light_lanes,
            "attempted": offered,
            "cut_commits": sum(c for _, _, c, _ in in_flight),
            "sched": {
                k: cut[k] - sched0[k]
                for k in ("tickets", "lanes", "device_dispatches", "host_chunks", "degraded")
            },
            "dispatches": between(probes.dispatches, t0, t_close),
            "seam_calls": between(probes.seam_calls, t0, t_close),
        }

    # --- what the harness reports ----------------------------------------

    def end_to_end(self) -> dict:
        from benchmark.lookup import percentile

        r = self.record
        lat = sorted(r["batch_s"])
        say(
            f"verify_batch: {len(lat)} batches in the window; the 95th "
            f"percentile has {len(lat) - int(0.95 * len(lat))} samples beyond it"
        )
        return {
            "verify_rate": r["sigs_verdicted"] / r["window_s"],
            "verify_batch_p95": 1e3 * percentile(lat, 95),
        }

    def counts(self) -> tuple:
        """(attempted, failed): commits offered in the window; those
        that got no verdict for another reason than the cut."""
        r = self.record
        return r["attempted"], r["attempted"] - r["verdicts"] - r["cut_commits"]

    # --- correct ----------------------------------------------------------

    def compare(self) -> list:
        verifier = reference.Verifier()
        want = []
        wrong_plan = 0
        for p, (_, plain, plain_vals) in enumerate(self.pool):
            err, idx, lanes = reference.light_verify(
                verifier, self.config["chain_id"], plain_vals, plain
            )
            want.append((err, idx))
            # the generator's own plan of what it corrupted
            planned = self.expected_bad.get(p)
            wrong_plan += (err, idx) != (
                ("invalid_signature", planned[1]) if planned else (None, None)
            )
            wrong_plan += lanes != self.light_lanes
        differ = 0
        compared = 0
        n = len(self.pool)
        for start, errs in self.results + self.late:
            for k, got in enumerate(errs):
                compared += 1
                differ += got != want[(start + k) % n]
        rejected = sum(1 for w in want if w[0] is not None)
        say(
            f"compare: verdicts compared={compared} pool={n} "
            f"reference rejects={rejected} by {sorted(set(self.expected_bad[p][0] for p in self.expected_bad))} "
            f"reference slow-path verifies={verifier.slow_path}"
        )
        return [
            ("verdicts_compared_min1", float(compared < 1), 0.0),
            ("commit_verdicts_differ", float(differ), 0.0),
            ("reference_vs_plan_differ", float(wrong_plan), 0.0),
            ("degraded_dispatches", float(self.record["sched"]["degraded"]), 0.0),
        ]

    def free(self) -> None:
        pass


def _corrupt(kind: str, signer, other, msg: bytes) -> bytes:
    """The signature a corrupted lane carries (chip_smoke.corrupt's
    five kinds, as far as a commit can express them: the key comes
    from the validator set, so ``wrong_key`` is another validator's
    signature and ``bad_key`` an undecodable key in the set)."""
    sig = signer.sign(msg)
    if kind == "sig_r_byte":
        return sig[:5] + bytes([sig[5] ^ 0x40]) + sig[6:]
    if kind == "sig_s_byte":
        return sig[:40] + bytes([sig[40] ^ 0x01]) + sig[41:]
    if kind == "wrong_key":
        return other.sign(msg)
    if kind == "bad_key":
        return sig  # a true signature; the set's key at this lane is undecodable
    if kind == "s_plus_L":
        s = int.from_bytes(sig[32:], "little") + reference.L
        return sig[:32] + s.to_bytes(32, "little")
    raise ValueError(f"unknown corruption {kind!r}")


def _plain_error(e):
    """A seam error as (kind, validator index)."""
    if e is None:
        return (None, None)
    name = type(e).__name__
    if name == "ErrInvalidSignature":
        # "invalid signature for validator {i} at height {h}"
        return ("invalid_signature", int(str(e).split("validator ")[1].split()[0]))
    if name == "ErrNotEnoughVotingPower":
        return ("not_enough_power", None)
    return (name, None)
