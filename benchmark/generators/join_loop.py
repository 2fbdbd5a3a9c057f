"""join-loop: nodes join a chain one after another by blocksync.

A closed loop with one joining node at a time. Each join builds a fresh
memdb node and drives BlockSyncReactor + StorePeerClient(source) with
the configuration's verify window on the configuration's backend,
routing left to the program's calibration. Joins run back to back until
the window closes; a timer reads how many blocks were applied at the
close, so the rate is over all the work and all the time of the window.

After the window, with the plain reference (benchmark/reference.py):
every finished join's height, block hash and app hash against the
source chain and the reference kvstore; the last finished join's every
block against the source's; the signatures the scheduler was given
against those light verification must read for the blocks applied.
"""

from __future__ import annotations

import asyncio
import threading
import time

from benchmark import chain, reference
from benchmark.probes import annotation, between, say


class Traffic:
    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.config = config
        self.mix = mix
        self.seed = seed
        self.gen, self.src, self.txs_by_height = chain.build_source(config, seed)
        self.limit = self.src.block_store.height()
        self.joins: list = []  # one dict a join, of every window of the run
        self._lanes_before = None
        self._last_finished = None  # the one join whose node is kept
        self.record: dict = {}
        self.fault = None  # the tests' hook: called with each fresh node

    # --- set-up -----------------------------------------------------------

    def warm_items(self) -> list:
        """One commit's signatures as kernel items: the dispatch that
        compiles or loads the cell's one program."""
        from cometbft_tpu.types.validation import _commit_sign_bytes

        vals = self.src.state.validators
        commit = self.src.block_store.load_seen_commit(1)
        return [
            (
                _commit_sign_bytes(self.gen.chain_id, commit, cs),
                vals.get_by_index(i).pub_key.key_bytes,
                cs.signature,
            )
            for i, cs in enumerate(commit.signatures)
        ]

    def warm(self, probes) -> None:
        """One whole join: every path of the window once, the native
        finalize lane built, the routing calibration fed."""
        for _ in range(self.mix["warm_joins"]):
            j = asyncio.run(self._join(time.perf_counter() + self.mix["join_timeout_s"]))
            if not j["caught_up"]:
                raise RuntimeError("the warm-up join did not catch up")
        self.joins.clear()
        self._last_finished = None

    # --- the window -------------------------------------------------------

    async def _join(self, deadline: float) -> dict:
        from cometbft_tpu.blocksync import BlockSyncReactor
        from cometbft_tpu.config.config import test_config
        from cometbft_tpu.node.inprocess import build_node
        from cometbft_tpu.utils.chaingen import StorePeerClient

        j = {"caught_up": False, "timed_out": False}
        with annotation("bench.node_build"):
            cfg = test_config(".")
            cfg.base.db_backend = "memdb"
            fresh = build_node(self.gen, None, config=cfg)
            if self.fault is not None:
                self.fault(fresh)
            caught = asyncio.Event()
            reactor = BlockSyncReactor(
                fresh.state, fresh.block_exec, fresh.block_store,
                on_caught_up=lambda st: caught.set(),
                verify_window=self.config["verify_window"],
            )
            reactor.tracer = fresh.tracer
            reactor.pool.set_peer_range(
                "src", StorePeerClient(self.src, self.mix["peer_delay_s"]),
                1, self.limit,
            )
        j["reactor"] = reactor
        j["node"] = fresh
        self.joins.append(j)
        await reactor.start()
        # waited out in short pieces: an annotation is in a trace only if
        # it began and ended inside it, and a join outlasts a traced slice
        while not caught.is_set() and time.perf_counter() < deadline:
            with annotation("bench.join_wait"):
                try:
                    await asyncio.wait_for(
                        caught.wait(), min(0.25, max(0.0, deadline - time.perf_counter()))
                    )
                except asyncio.TimeoutError:
                    pass
        j["caught_up"] = caught.is_set()
        await reactor.stop()
        self._settle(j)
        return j

    def _settle(self, j: dict) -> None:
        """Keep of a join that has ended what the window's record and
        the comparison read, and let its node go: a node is a chain's
        worth of objects, and the nodes of every join of a window, held
        to its end, were the collector's to walk in the joins after
        them. The last finished join's node stays, for the comparison of
        its every block."""
        node, reactor = j["node"], j["reactor"]
        j["blocks_applied"] = reactor.blocks_applied
        j["pipeline"] = dict(reactor.pipeline_stats)
        j["tracer"] = node.tracer
        if j["caught_up"]:
            h = node.block_store.height()
            state = node.state_store.load()
            j["tip"] = (
                h, node.block_store.load_block(h).hash(),
                state.app_hash, state.last_block_height,
            )
            if self._last_finished is not None:
                del self._last_finished["node"]
            self._last_finished = j
        else:
            del j["node"]
        del j["reactor"]

    @staticmethod
    def _applied(j: dict) -> int:
        """Blocks a join has applied, also while it runs (the timer at
        the window's close reads from a thread of its own)."""
        reactor = j.get("reactor")
        return reactor.blocks_applied if reactor is not None else j["blocks_applied"]

    def window(self, probes, seconds: float) -> None:
        from cometbft_tpu.crypto import scheduler as crypto_sched

        sched = crypto_sched.scheduler()
        sched0 = sched.stats()
        if self._lanes_before is None:
            self._lanes_before = sched0["lanes"]
        first = len(self.joins)  # a traced run's slice is a window more
        cut: dict = {}
        t0 = time.perf_counter()
        t_end = t0 + seconds

        def at_close():
            cut["t"] = time.perf_counter()
            cut["blocks"] = sum(self._applied(j) for j in self.joins[first:])
            cut["sched"] = sched.stats()

        timer = threading.Timer(seconds, at_close)
        timer.start()
        timeout = self.mix["join_timeout_s"]

        async def loop():
            while time.perf_counter() < t_end:
                j = await self._join(min(t_end, time.perf_counter() + timeout))
                if not j["caught_up"] and time.perf_counter() < t_end:
                    j["timed_out"] = True

        try:
            asyncio.run(loop())
        finally:
            timer.join()
        if not sched.drain(timeout=60.0):
            raise RuntimeError("verify scheduler did not drain")
        self.record = self._reduce(probes, self.joins[first:], t0, seconds, cut, sched0)

    def _reduce(self, probes, joins, t0, seconds, cut, sched0) -> dict:
        spans = []
        pipeline = {"reused": 0, "dispatched": 0, "predispatched": 0, "discarded": 0}
        for j in joins:
            for e in j["tracer"].snapshot():
                if e["name"].startswith("blocksync.window.") and e["ph"] == "X":
                    spans.append(
                        {
                            "name": e["name"],
                            "dur_s": e["dur_ns"] / 1e9,
                            "jobs": e["args"].get("jobs"),
                        }
                    )
            for k in pipeline:
                pipeline[k] += j["pipeline"][k]
        t1 = cut["t"]
        seam = between(probes.seam_calls, t0, t1)
        blocks_total = sum(j["blocks_applied"] for j in joins)
        return {
            "window_s": seconds,
            "blocks_at_close": cut["blocks"],
            "blocks_applied": blocks_total,
            "spans": spans,
            "pipeline": pipeline,
            "sched": {
                k: cut["sched"][k] - sched0[k]
                for k in ("tickets", "lanes", "device_dispatches", "host_chunks", "degraded")
            },
            "dispatches": between(probes.dispatches, t0, t1),
            "seam_calls": seam,
            "attempted": sum(s["commits"] for s in seam),
        }

    # --- what the harness reports ----------------------------------------

    def end_to_end(self) -> dict:
        r = self.record
        return {"catchup_rate": r["blocks_at_close"] / r["window_s"]}

    def counts(self) -> tuple:
        """(attempted, failed): commits offered for verification in the
        window; every commit of a join that timed out."""
        failed = sum(
            self.limit - 1 for j in self.joins if j["timed_out"]
        )
        return self.record["attempted"], failed

    # --- correct ----------------------------------------------------------

    def compare(self) -> list:
        """[(name, number, limit)], after the window."""
        cfg = self.config
        src_store = self.src.block_store
        kv = reference.KvStore()
        app_hashes = [kv.apply_block(txs) for txs in self.txs_by_height]
        finished = [j for j in self.joins if j["caught_up"]]
        wrong_tip = 0
        for j in finished:
            h, block_hash, app_hash, last_height = j["tip"]
            ok = (
                h >= self.limit - 1
                and block_hash == src_store.load_block(h).hash()
                and app_hash == app_hashes[h - 1]
                and last_height == h
            )
            wrong_tip += not ok
        # every block of the last finished join, in height order
        wrong_blocks = 0
        if finished:
            store = self._last_finished["node"].block_store
            for h in range(1, store.height() + 1):
                blk = store.load_block(h)
                ref = src_store.load_block(h)
                wrong_blocks += (
                    blk is None
                    or blk.hash() != ref.hash()
                    or list(blk.data.txs) != self.txs_by_height[h - 1]
                )
                if h < store.height():
                    wrong_blocks += (
                        src_store.load_block(h + 1).header.app_hash
                        != app_hashes[h - 1]
                    )
        # signatures light verification must read: the plain reference
        # walks the source's commits (block h is applied on the commit
        # that block h+1 carries)
        verifier = reference.Verifier()
        vals = [
            (v.pub_key.key_bytes, v.voting_power)
            for v in self.src.state.validators.validators
        ]
        lanes_by_height = []
        bad_commits = 0
        for h in range(1, self.limit):
            c = src_store.load_seen_commit(h)
            err, _, lanes = reference.light_verify(
                verifier, cfg["chain_id"], vals, _plain_commit(c)
            )
            bad_commits += err is not None
            lanes_by_height.append(lanes)
        need = 0
        for j in self.joins:
            need += sum(lanes_by_height[: j["blocks_applied"]])
        sched_total = self._sched_lanes_all()
        unverified = max(0, need - sched_total)
        numbers = [
            ("joins_finished_min1", float(len(finished) < 1), 0.0),
            ("joins_wrong_tip", float(wrong_tip), 0.0),
            ("blocks_differ", float(wrong_blocks), 0.0),
            ("source_commits_bad", float(bad_commits), 0.0),
            ("sigs_unverified", float(unverified), 0.0),
            ("joins_timed_out", float(sum(j["timed_out"] for j in self.joins)), 0.0),
            ("degraded_dispatches", float(self.record["sched"]["degraded"]), 0.0),
        ]
        say(
            f"compare: joins={len(self.joins)} finished={len(finished)} "
            f"sigs needed={need} scheduler lanes={sched_total} "
            f"reference slow-path verifies={verifier.slow_path}"
        )
        return numbers

    def _sched_lanes_all(self) -> int:
        """Signatures handed to the scheduler by every join of the
        window, those a cut join had in flight at the close included."""
        from cometbft_tpu.crypto import scheduler as crypto_sched

        return crypto_sched.scheduler().stats()["lanes"] - self._lanes_before

    def free(self) -> None:
        for j in self.joins:
            j.pop("node", None)
        self._last_finished = None


def _plain_commit(c) -> dict:
    return {
        "height": c.height,
        "round": c.round,
        "block_hash": c.block_id.hash,
        "parts_total": c.block_id.part_set_header.total,
        "parts_hash": c.block_id.part_set_header.hash,
        "sigs": [
            (s.block_id_flag, s.timestamp_ns, s.signature) for s in c.signatures
        ],
    }
