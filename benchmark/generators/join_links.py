"""join-links: nodes join a chain one after another by blocksync from
TEN peers, each behind a link of a stated rate and round trip.

``join_loop``'s closed loop, timer, counts and comparison, with the one
in-process peer replaced by the configuration's peers: views of the one
source store, each behind a ``Link``. A link is this generator's own
plain model, not the program's ``FlowRate``: a request reaches the peer
half a round trip after it is sent; responses leave ONE at a time, each
``bytes / rate`` after the later of its request's arrival and the
link's last departure; a response is handed over half a round trip
after it left, never earlier. If the loop wakes late it is handed over
late, and the link's schedule does not slip. ``slow_peers`` of the
peers, drawn from the seed for each join, have the slow rate, under the
pool's receive-rate floor. Every link logs (request, due, handed over,
bytes).

After the window, besides ``join_loop``'s comparison, with the plain
reference ``benchmark/reference_links.py``: no peer at or above the
floor was banned, in finished and cut joins alike; every link's log
obeys the serial-link law; no block came from a peer the generator did
not announce; the rate is under what the links could carry. Whether the
slow peer was banned is said, not compared: a join cut by the window's
close may end before the evidence is in.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark import reference_links
from benchmark.generators import join_loop
from benchmark.probes import annotation, say

BAN = "blocksync.pool.ban"
FETCH_WAIT = "blocksync.window.fetch_wait"


class Link:
    def __init__(self, rate_bps: float, rtt_s: float) -> None:
        self.rate_bps = rate_bps
        self.half = rtt_s / 2.0
        self.free_at = 0.0  # when the last response has left
        self.metered = True
        self.log: list = []  # [request, due, handed over or None, bytes]

    def take(self, now: float, nbytes: int) -> list:
        """Schedule a response to a request sent ``now``; its row."""
        self.free_at = max(now + self.half, self.free_at) + nbytes / self.rate_bps
        row = [now, self.free_at + self.half, None, nbytes]
        self.log.append(row)
        return row


class LinkPeer:
    """A view of the source store behind a link: the pool's
    ``request_block`` interface."""

    def __init__(self, src, link: Link, clock=time.perf_counter, sleep=asyncio.sleep):
        self.src = src
        self.link = link
        self.clock = clock
        self.sleep = sleep

    async def request_block(self, height: int):
        blk = self.src.block_store.load_block(height)
        if blk is None:
            return None
        row = self.link.take(self.clock(), len(blk._raw_bytes))
        while self.link.metered and (wait := row[1] - self.clock()) > 0:
            await self.sleep(wait)
        row[2] = self.clock()
        return blk


def draw_slow(seed: int, join: int, peers: list, n: int) -> set:
    """Which peers have the slow link in the run's ``join``-th join."""
    rng = np.random.default_rng([seed, 3, join])
    return {peers[i] for i in rng.choice(len(peers), size=n, replace=False)}


class Traffic(join_loop.Traffic):
    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        super().__init__(config, mix, seed)
        self.peers = [f"p{i}" for i in range(config["peers"])]
        self.unmetered = 0  # the tests' fault: so many sound links hand over at once
        self._joins_started = 0
        self._check_program_and_chain()

    def _check_program_and_chain(self) -> None:
        """What the configuration states of the chain and the program,
        held to both before anything is timed."""
        from cometbft_tpu.blocksync import pool as pool_mod

        sizes = [
            len(self.src.block_store.load_block(h)._raw_bytes)
            for h in range(2, self.limit + 1)
        ]
        stated = self.config["block_wire_bytes"]
        # the height's decimal digits (in the tx) and its varints grow
        if sizes[0] != stated or not stated <= min(sizes) <= max(sizes) <= stated + 8:
            raise RuntimeError(
                f"block_wire_bytes is stated as {stated}; the chain's blocks "
                f"from height 2 on encode to {min(sizes)}..{max(sizes)} bytes"
            )
        floor = getattr(pool_mod, "MIN_RECV_RATE", None)
        if floor is None:
            say("links: the program's pool has no receive-rate floor")
        elif floor != self.config["min_recv_rate_bps"]:
            raise RuntimeError(
                f"the pool's floor is {floor} B/s, the configuration states "
                f"{self.config['min_recv_rate_bps']}"
            )

    # --- the peers of one join ---------------------------------------------

    def _links(self) -> tuple:
        """({peer: Link}, {peer: rate}) of the next join."""
        cfg = self.config
        slow = draw_slow(self.seed, self._joins_started, self.peers, cfg["slow_peers"])
        self._joins_started += 1
        rates = {
            p: cfg["slow_link_rate_bps"] if p in slow else cfg["link_rate_bps"]
            for p in self.peers
        }
        links = {p: Link(rates[p], cfg["link_rtt_ms"] / 1e3) for p in self.peers}
        sound = [p for p in self.peers if p not in slow]
        for p in sound[: self.unmetered]:
            links[p].metered = False
        return links, rates

    async def _join(self, deadline: float) -> dict:
        """join_loop's, with the configuration's peers (all of them
        announce the whole chain before the reactor starts) and its
        own end of a join."""
        from cometbft_tpu.blocksync import BlockSyncReactor
        from cometbft_tpu.config.config import test_config
        from cometbft_tpu.node.inprocess import build_node

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
            j["links"], j["rates"] = self._links()
            for peer, link in j["links"].items():
                reactor.pool.set_peer_range(peer, LinkPeer(self.src, link), 1, self.limit)
        j["reactor"] = reactor
        j["node"] = fresh
        j["t0"] = time.perf_counter()
        self.joins.append(j)
        await reactor.start()
        # A join ends when the node has applied the last block it can
        # verify (block h needs h+1's commit), seen here within 20 ms,
        # not at the reactor's next once-a-second caught-up check: that
        # check drifts by 0-0.2 s a period, a join of 4.5 s ended
        # anywhere from 5.0 to 5.8 s, and eight of them moved the
        # window's count by a join's worth (six seeds spread 7.3%,
        # PERF.md, PR 37)
        last = self.limit - 1
        while (
            reactor.blocks_applied < last and not caught.is_set()
            and time.perf_counter() < deadline
        ):
            with annotation("bench.join_wait"):
                try:
                    await asyncio.wait_for(
                        caught.wait(), min(0.02, max(0.0, deadline - time.perf_counter()))
                    )
                except asyncio.TimeoutError:
                    pass
        j["caught_up"] = caught.is_set() or reactor.blocks_applied >= last
        await reactor.stop()
        self._settle(j)
        return j

    def _settle(self, j: dict) -> None:
        pool = j["reactor"].pool
        # a pool that counts nothing (the parent's) leaves the link logs
        j["pool"] = pool.stats() if hasattr(pool, "stats") else None
        j["banned"] = sorted(pool.banned_until)
        super()._settle(j)

    # --- the window's record ---------------------------------------------------

    def _reduce(self, probes, joins, t0, seconds, cut, sched0) -> dict:
        record = super()._reduce(probes, joins, t0, seconds, cut, sched0)
        record["links"] = self._reduce_links(joins)
        return record

    def _reduce_links(self, joins: list) -> dict:
        cfg = self.config
        rows = []
        head_wait_s = None
        for j in joins:
            slow = reference_links.under_floor(cfg, j["rates"])
            handed = {
                p: [r for r in link.log if r[2] is not None]
                for p, link in j["links"].items()
            }
            requests = [r[0] for link in j["links"].values() for r in link.log]
            arrivals = [r[2] for rs in handed.values() for r in rs]
            row = {
                "slow": sorted(slow),
                "caught_up": j["caught_up"],
                "blocks_applied": j["blocks_applied"],
                "fetch_s": max(arrivals) - min(requests) if arrivals else 0.0,
                "log_blocks": {p: len(rs) for p, rs in handed.items()},
                "log_bytes": {p: sum(r[3] for r in rs) for p, rs in handed.items()},
                "banned": j["banned"],
                "pool": j["pool"],
                "rate_ban_s": None,
            }
            for e in j["tracer"].snapshot():
                if e["ph"] != "X":
                    continue
                args = e["args"]
                if e["name"] == FETCH_WAIT and args.get("buffered") is not None:
                    head_wait_s = (head_wait_s or 0.0) + (
                        e["dur_ns"] / 1e9 if args["buffered"] > 0 else 0.0
                    )
                elif (
                    e["name"] == BAN and args.get("reason") == "rate"
                    and args.get("peer") in slow and row["rate_ban_s"] is None
                ):
                    row["rate_ban_s"] = (e["ts_ns"] + e["dur_ns"]) / 1e9 - j["t0"]
            rows.append(row)
        self._say_links(rows)
        return {
            "joins": rows,
            "head_wait_s": head_wait_s,
            "sound_capacity_bps": (cfg["peers"] - cfg["slow_peers"]) * cfg["link_rate_bps"],
        }

    @staticmethod
    def _say_links(rows: list) -> None:
        """For PERF.md, from the generator's own logs, so that a program
        whose pool counts nothing is on record too."""
        for i, r in enumerate(rows):
            blocks = sum(r["log_blocks"].values())
            from_slow = sum(r["log_blocks"][p] for p in r["slow"])
            say(
                f"links: join {i} slow={r['slow']} caught_up={r['caught_up']} applied "
                f"{r['blocks_applied']} of {blocks} handed over in {r['fetch_s']!r} s, "
                f"{from_slow} by the slow peer; banned={r['banned']} "
                f"rate ban after {r['rate_ban_s']!r} s"
            )
            if r["pool"]:
                p = r["pool"]
                say(
                    f"links: join {i} pool head_waits={p['head_waits']} "
                    f"head_wait_s={p['head_wait_s']!r} peers="
                    + " ".join(
                        f"{pid}:{s['blocks']}b/{s['requests']}r/{s['redone']}redone"
                        f"/{s['timeouts']}to/{s['rate_bps'] and round(s['rate_bps'])}Bps"
                        f"/{s['banned'] and s['banned'][0]}"
                        for pid, s in sorted(p["peers"].items())
                    )
                )

    # --- correct -------------------------------------------------------------------

    def compare(self) -> list:
        numbers = super().compare()
        cfg = self.config
        sound_banned = overrun = before_rtt = unknown = 0
        slow_banned = 0
        for j in self.joins:
            slow = reference_links.under_floor(cfg, j["rates"])
            sound_banned += len(set(j["banned"]) - slow)
            slow_banned += bool(set(j["banned"]) & slow)
            logs = {p: link.log for p, link in j["links"].items()}
            law = reference_links.check_join(cfg, j["rates"], logs)
            overrun += law["overrun_bytes"]
            before_rtt += law["before_rtt"]
            unknown += max(0, j["blocks_applied"] - law["handed_over"])
            if j["pool"]:
                unknown += sum(
                    s["blocks"] for p, s in j["pool"]["peers"].items() if p not in logs
                )
        ceiling = reference_links.link_ceiling_blocks_per_s(cfg)
        rate = self.end_to_end()["catchup_rate"]
        numbers += [
            ("sound_peers_banned", float(sound_banned), 0.0),
            ("link_overrun_bytes", float(overrun), 0.0),
            ("responses_before_rtt", float(before_rtt), 0.0),
            ("rate_over_link_ceiling", float(rate > ceiling), 0.0),
            ("blocks_from_unknown_peer", float(unknown), 0.0),
        ]
        finished = sum(j["caught_up"] for j in self.joins)
        say(
            f"compare: links: the slow peer was banned in {slow_banned} of "
            f"{len(self.joins)} joins ({finished} finished); the links carry "
            f"{ceiling!r} blocks/s at the most, the run read {rate!r}"
        )
        return numbers

    def free(self) -> None:
        super().free()
        for j in self.joins:
            j.pop("links", None)


# --- the planted faults this mix adds to benchmark/faults.py's ---------------------


def link_unmetered(traffic):
    """A link that is no link: one sound peer hands its blocks over at
    once."""
    traffic.unmetered = 1

    def undo():
        traffic.unmetered = 0

    return undo


def floor_on_everyone(traffic):
    """The pool's floor above every link's rate, judged on a third of a
    second (a sound peer's queue is 0.9 s deep in a join's first deal
    and seldom reaches the program's full second): sound peers are
    banned."""
    from cometbft_tpu.blocksync import pool as pool_mod

    old = pool_mod.MIN_RECV_RATE, pool_mod.RATE_EVIDENCE_S
    pool_mod.MIN_RECV_RATE, pool_mod.RATE_EVIDENCE_S = 1_000_000, 0.3

    def undo():
        pool_mod.MIN_RECV_RATE, pool_mod.RATE_EVIDENCE_S = old

    return undo


FAULTS = {"link_unmetered": link_unmetered, "floor_on_everyone": floor_on_everyone}
