"""The deployment ``val150-kvstore-links`` and its cell
``val150.catchup-delayed`` (BENCHMARK.json), as far as the CPU can show
them: the pool's receive-rate floor on a fake clock against links that
obey the benchmark's serial-link law, the ban that takes requests back,
the requester that waits on an event, the one-peer window as deep as
before, the generator's link model against ``reference_links``, the
five readers on made records, and the harness end to end at 8
validators. Times and rates come from the chip alone (PERF.md).
"""

import asyncio
import json
import os

import pytest

from benchmark import faults, links, lookup, reference_links, run
from benchmark.generators import join_links
from benchmark.tests import tiny
from cometbft_tpu.blocksync import BlockSyncReactor
from cometbft_tpu.blocksync import pool as pool_mod
from cometbft_tpu.blocksync.pool import BlockPool, PoolPeer
from cometbft_tpu.node.inprocess import build_node, make_genesis
from cometbft_tpu.ops import ed25519 as ops_ed
from cometbft_tpu.utils.chaingen import StorePeerClient, make_chain

CELL = "val150.catchup-delayed"
NEW_METRICS = (
    "link_utilisation.catchup",
    "head_of_line_wait_share.catchup",
    "slow_peer_block_share.catchup",
    "slow_peer_ban_s.catchup",
    "blocks_per_window.catchup",
)
SEED = 2_147_483_783  # past 32 signed bits, as the driver's can be
BLOCK = 15_489
RTT = 0.1


def run_async(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def monotonic(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(pool_mod, "_now", c.monotonic)
    return c


class Raw:
    """A block as far as the pool looks at one: its wire bytes."""

    def __init__(self, n: int) -> None:
        self._raw_bytes = b"\0" * n


# --- (a) the floor, on a fake clock, against links that obey the law -------------


def simulate(clock, rates, queue, seconds, holds=(), late_handover=False, block=BLOCK):
    """A pool whose peers each get ``queue`` requests of ``block`` bytes at once, behind
    serial links of ``rates`` and a 100 ms round trip; the monitor
    beats every 0.1 s except inside ``holds`` ((start, length) pairs,
    seconds from the start), where the loop runs nothing: what arrives
    then is handed over when the hold ends, before the late beat or
    (``late_handover``) after it."""
    t0 = clock.now
    pool = BlockPool(1)
    pool._stopped = True  # no loop here: nothing spawns
    pool._last_tick = t0
    due = []
    h = 1
    for name, rate in rates.items():
        peer = pool.peers[name] = PoolPeer(name, None, base=1, height=10_000)
        leaves = t0 + RTT / 2
        for _ in range(queue):
            pool._sent(peer, h)
            leaves += block / rate
            due.append((leaves + RTT / 2, name, h))
            h += 1
    due.sort()
    beat = t0
    while beat < t0 + seconds:
        beat += pool_mod.MONITOR_TICK_S
        for start, length in holds:
            if t0 + start <= beat < t0 + start + length:
                beat = t0 + start + length
        handed = [d for d in due if d[0] <= beat]
        due = [d for d in due if d[0] > beat]

        def hand_over():
            for at, name, height in handed:
                peer = pool.peers[name]
                if height in peer.inflight:  # not taken back by a ban
                    for start, length in holds:  # seen when the hold ends
                        if t0 + start <= at < t0 + start + length:
                            at = t0 + start + length
                    clock.now = max(at, clock.now)
                    pool._received(peer, height, Raw(block))
                    pool._settle(peer, height)

        if not late_handover:
            hand_over()
        clock.now = beat
        pool._tick(beat)
        if late_handover:
            hand_over()
    return pool


@pytest.mark.parametrize(
    "rate, banned",
    [(65_536, True), (100_000, True), (131_072, False), (200_000, False), (512_000, False)],
)
def test_the_floor_bans_a_link_under_it_and_no_other(clock, rate, banned):
    """26 requests a peer, a join's first deal: the slow link's last
    request would wait 6 s, the sound link's 0.9 s."""
    pool = simulate(clock, {"p": rate, "q": 512_000}, 26, 3.0)
    assert ("p" in pool.banned_until) is banned
    assert "q" not in pool.banned_until
    stats = pool.stats()["peers"]["p"]
    if banned:
        reason, at = stats["banned"]
        assert reason == "rate"
        # one second of evidence from the first response, then the next beat
        first = RTT + BLOCK / rate
        assert pool_mod.RATE_EVIDENCE_S + first <= at <= pool_mod.RATE_EVIDENCE_S + first + 0.25
        assert stats["redone"] == 26 - stats["blocks"] > 0
        assert not pool.peers["p"].inflight
    else:
        assert stats["banned"] is None and stats["redone"] == 0
        # what was counted is what the link carried, the round trip in it
        assert rate * 0.8 <= stats["rate_bps"] <= rate


@pytest.mark.parametrize("queue", [1, 2, 40, 120])
def test_a_sound_link_is_never_banned_whatever_its_queue(clock, queue):
    """One block at a time a sound peer is settled within a round trip
    and never reaches the evidence; with 3.6 s of work queued it is
    judged, and reads 512,000 B/s less at most one round trip."""
    pool = simulate(clock, {"p": 512_000}, queue, 5.0)
    assert not pool.banned_until
    assert pool.peers["p"].blocks == queue


@pytest.mark.parametrize("late_handover", [False, True])
def test_a_held_loop_is_not_the_peers_slowness(clock, late_handover):
    """The qa175.catchup trap: ONE peer with no limit to speak of, five
    400 KB blocks in flight, and a synchronous apply that holds the loop
    for 5 s from 0.15 s on. Everything arrived within a tenth of a
    second and is seen 5 s late: 80 KB/s by the wall clock."""
    pool = simulate(
        clock, {"src": 50_000_000}, 5, 6.0, holds=[(0.15, 5.0)],
        late_handover=late_handover, block=400_000,
    )
    assert not pool.banned_until
    assert pool.peers["src"].blocks == 5
    assert 4.8 <= pool._held_s <= 5.0  # taken off the running clock
    # over the time the loop ran, not the 5 s it did not
    assert pool.stats()["peers"]["src"]["rate_bps"] > 1_000_000


def test_a_slow_link_is_banned_beside_a_loop_held_half_the_time(clock):
    """val150's apply holds the loop 0.2 s of every 0.4: the slow
    link's bytes still arrive at 65,536 B/s by the wall clock, and a
    beat that comes on time finds its oldest request still waiting."""
    holds = [(0.3 + 0.4 * k, 0.2) for k in range(10)]
    pool = simulate(clock, {"slow": 65_536, "sound": 512_000}, 26, 3.0, holds=holds)
    assert list(pool.banned_until) == ["slow"]
    assert pool.peers["slow"].banned[0] == "rate"
    # its first block is seen when the hold it fell into ends (0.5 s); a
    # second later the loop is held again, and judges on the beat after
    assert pool.peers["slow"].banned[1] == pytest.approx(1.8)


def test_a_peer_that_delivered_nothing_is_the_timeouts_case(clock):
    pool = BlockPool(1)
    pool._stopped = True
    pool._last_tick = clock.now
    peer = pool.peers["mute"] = PoolPeer("mute", None, base=1, height=9)
    pool._sent(peer, 1)
    for _ in range(50):
        clock.now += pool_mod.MONITOR_TICK_S
        pool._tick(clock.now)
    assert not pool.banned_until  # 5 s of silence: REQUEST_TIMEOUT_S decides
    assert pool_mod.REQUEST_TIMEOUT_S == 10.0
    assert pool_mod.MIN_RECV_RATE == 131_072


def test_pick_peer_prefers_what_was_delivered_over_a_short_queue(clock):
    pool = BlockPool(1)
    pool._stopped = True
    for name, got in (("fast", 512_000), ("slow", 65_536), ("new", 0)):
        pool.peers[name] = PoolPeer(name, None, base=1, height=99, bytes=got, busy_s=1.0)
    pool.peers["new"].busy_s = 0.0  # never asked yet: tried first
    assert pool._pick_peer(1).peer_id == "new"
    del pool.peers["new"]
    # seven requests queued at the fast peer still beat one at the slow
    for h in range(1, 7):
        pool._sent(pool.peers["fast"], h)
    assert pool._pick_peer(9).peer_id == "fast"
    for h in range(7, 10):
        pool._sent(pool.peers["fast"], h)
    assert pool._pick_peer(10).peer_id == "slow"
    # a peer that has had requests pending and delivered nothing comes last
    mute = pool.peers["mute"] = PoolPeer("mute", None, base=1, height=99)
    pool._sent(mute, 50)
    clock.now += 0.5
    assert pool._pick_peer(11).peer_id == "slow"


# --- (b) bans take requests back; requesters wait on events ---------------------


class StubClient:
    def __init__(self, name, hang=False):
        self.name = name
        self.hang = hang
        self.requests = []

    async def request_block(self, height):
        self.requests.append(height)
        if self.hang:
            await asyncio.Event().wait()
        return Raw(100 + height)


@pytest.mark.parametrize("reason", ["rate", "bad_block", "removed"])
def test_a_ban_takes_back_what_is_in_flight_and_it_lands_elsewhere(reason):
    async def main():
        slow, fast = StubClient("slow", hang=True), StubClient("fast", hang=True)
        pool = BlockPool(1)
        pool.set_peer_range("slow", slow, 1, 6)
        await asyncio.sleep(0.05)
        assert sorted(slow.requests) == [1, 2, 3, 4, 5, 6]
        fast.hang = False
        pool.set_peer_range("fast", fast, 1, 6)
        pool.blocks[3] = (Raw(1), "slow")  # an earlier delivery, buffered
        if reason == "rate":
            pool.ban_peer("slow", "rate", 1234.5)
        elif reason == "bad_block":
            pool.redo_request(3, ban_peer="slow")
        else:
            pool.remove_peer("slow")
        # no timeout is waited out: a few passes of the loop are enough
        for _ in range(5):
            await asyncio.sleep(0)
        assert sorted(pool.blocks) == [1, 2, 3, 4, 5, 6]
        assert {pid for _, pid in pool.blocks.values()} == {"fast"}
        assert pool_mod.REQUEST_TIMEOUT_S == 10.0
        if reason != "removed":
            s = pool.stats()["peers"]["slow"]
            assert (s["redone"], s["banned"][0], s["requests"]) == (6, reason, 6)
            assert "slow" in pool.banned_peers()
        assert pool.stats()["peers"]["fast"]["bytes"] == sum(100 + h for h in range(1, 7))
        pool.stop()

    run_async(main())


def test_a_requester_with_no_peer_wakes_on_the_event_not_on_a_clock():
    async def main():
        pruned, full = StubClient("pruned"), StubClient("full")
        pool = BlockPool(5)
        pool.set_peer_range("pruned", pruned, 10, 20)  # serves nothing below 10
        await asyncio.sleep(0.12)  # two of the old 50 ms polls
        assert 5 in pool._tasks and 5 not in pool.blocks and not pruned.requests[:0]
        assert min(pruned.requests) == 10
        pool.set_peer_range("full", full, 1, 20)
        for _ in range(4):
            await asyncio.sleep(0)  # no time passes
        assert pool.blocks[5][1] == "full" and 5 not in pool._tasks
        pool.stop()

    run_async(main())


# --- (c) the one-peer window is as deep as on the parent ------------------------


@pytest.fixture(scope="module")
def source_chain():
    gen, pvs = make_genesis(4, chain_id="links-one-peer")
    return gen, make_chain(gen, [pv.priv_key for pv in pvs], 120, txs_per_block=1)


@pytest.mark.parametrize("window", [32, 40])
def test_one_instant_peer_keeps_two_windows_and_two_in_flight(source_chain, window):
    gen, src = source_chain

    async def main():
        fresh = build_node(gen, None)
        caught = asyncio.Event()
        reactor = BlockSyncReactor(
            fresh.state, fresh.block_exec, fresh.block_store,
            on_caught_up=lambda st: caught.set(), verify_window=window,
        )
        reactor.tracer = fresh.tracer
        assert reactor.pool.tracer is fresh.tracer  # the pool's bans land there too
        reactor.pool.set_peer_range("src", StorePeerClient(src), 1, src.block_store.height())
        await reactor.start()
        await asyncio.wait_for(caught.wait(), 60)
        await reactor.stop()
        return reactor, fresh

    reactor, fresh = run_async(main())
    assert reactor.pool.blocks_hwm == 2 * window + 2
    stats = reactor.pool.stats()
    assert stats["peers"]["src"]["banned"] is None and not reactor.pool.banned_until
    assert stats["peers"]["src"]["blocks"] >= 119 and stats["peers"]["src"]["bytes"] > 0
    waits = [
        e["args"] for e in fresh.tracer.snapshot()
        if e["name"] == "blocksync.window.fetch_wait"
    ]
    assert waits and all(set(a) == {"buffered", "head_peer"} for a in waits)


# --- (d) the generator's link model against the plain reference -------------------


def drive_link(rate, rtt_s, requests, wake_late=0.0):
    """``requests`` = [(sent at, bytes)] through one LinkPeer on a fake
    clock whose sleeps overshoot by ``wake_late``; the link's log."""
    now = [0.0]

    class Store:
        def load_block(self, height):
            return Raw(height)

    async def sleep(dt):
        now[0] += dt + wake_late

    link = join_links.Link(rate, rtt_s)
    peer = join_links.LinkPeer(
        type("Src", (), {"block_store": Store()}), link, clock=lambda: now[0], sleep=sleep
    )

    async def main():
        for at, nbytes in requests:
            now[0] = max(now[0], at)
            sent = now[0]
            await peer.request_block(nbytes)
            now[0] = sent  # requests are pipelined: the next does not wait
        return link.log

    return asyncio.run(main())


@pytest.mark.parametrize("wake_late", [0.0, 0.25])
def test_a_link_sends_one_response_at_a_time_and_never_early(wake_late):
    requests = [(0.0, 1000), (0.0, 3000), (0.01, 500), (2.0, 1000)]
    log = drive_link(10_000, 0.1, requests, wake_late)
    # 1000 B leave at 0.05 + 0.1, 3000 more at 0.45, 500 at 0.5; the
    # link then idles, and the last is a request of its own at 2.0
    assert [r[1] for r in log] == pytest.approx([0.2, 0.5, 0.55, 2.2])
    for row in log:
        assert row[2] >= row[1] and row[2] - row[1] <= wake_late + 1e-9
    law = reference_links.check_log(10_000, 0.1, log)
    assert law == {"overrun_bytes": 0.0, "before_rtt": 0, "handed_over": 4, "bytes": 5500}


def test_the_reference_catches_a_link_that_is_none():
    log = drive_link(10_000, 0.1, [(0.0, 1000)] * 8)
    at_once = [[r[0], r[1], r[0], r[3]] for r in log]  # handed over as asked
    law = reference_links.check_log(10_000, 0.1, at_once)
    assert law["before_rtt"] == 8 and law["overrun_bytes"] >= 7000
    # all eight at the instant the first was due: no single one is
    # before its round trip, the token law still says seven too many
    bunched = [[r[0], r[1], log[0][1], r[3]] for r in log]
    law = reference_links.check_log(10_000, 0.1, bunched)
    assert law["before_rtt"] == 0 and law["overrun_bytes"] >= 7000
    # a request taken back holds the link and is never handed over
    taken = [list(r) for r in log]
    taken[3][2] = None
    law = reference_links.check_log(10_000, 0.1, taken)
    assert (law["overrun_bytes"], law["handed_over"]) == (0.0, 7)


def test_the_reference_reads_floor_and_ceiling_off_the_configuration():
    config = lookup.load_cell(lookup.load_spec(), CELL)["config_data"]
    rates = {f"p{i}": config["link_rate_bps"] for i in range(10)}
    rates["p4"] = config["slow_link_rate_bps"]
    assert reference_links.under_floor(config, rates) == {"p4"}
    rates["p4"] = config["min_recv_rate_bps"]
    assert reference_links.under_floor(config, rates) == set()
    assert reference_links.link_ceiling_blocks_per_s(config) == pytest.approx(330.56, abs=0.01)
    assert 9 * 512_000 / 15_489 == pytest.approx(297.5, abs=0.05)


def test_the_slow_peer_is_drawn_from_the_seed_for_each_join():
    peers = [f"p{i}" for i in range(10)]
    draws = [join_links.draw_slow(SEED, k, peers, 1) for k in range(40)]
    assert draws == [join_links.draw_slow(SEED, k, peers, 1) for k in range(40)]
    assert draws != [join_links.draw_slow(SEED + 1, k, peers, 1) for k in range(40)]
    assert all(len(d) == 1 for d in draws) and len(set().union(*draws)) >= 8
    assert len(join_links.draw_slow(SEED, 0, peers, 3)) == 3


# --- (e) the five readers on a made record and on nothing -----------------------


def made_record() -> dict:
    peers = {
        f"p{i}": {"requests": 120, "blocks": 110, "bytes": 110 * BLOCK, "rate_bps": 5e5,
                  "timeouts": 0, "banned": None, "redone": 0}
        for i in range(10)
    }
    peers["p3"] = dict(peers["p3"], blocks=5, bytes=5 * BLOCK, banned=("rate", 1.1), redone=21)
    join = {
        "slow": ["p3"], "caught_up": True, "blocks_applied": 1000, "fetch_s": 4.0,
        "pool": {"peers": peers, "head_waits": 9, "head_wait_s": 1.2},
        "banned": ["p3"], "rate_ban_s": 1.1,
    }
    cut = dict(join, caught_up=False, blocks_applied=500, fetch_s=2.0, rate_ban_s=None)
    return {
        "window_s": 10.0,
        "spans": [
            {"name": "blocksync.window.verify_wait", "dur_s": 0.1, "jobs": 127},
            {"name": "blocksync.window.verify_wait", "dur_s": 0.1, "jobs": 13},
            {"name": "blocksync.window.apply", "dur_s": 0.2, "jobs": 127},
        ],
        "links": {"joins": [join, cut], "head_wait_s": 2.5, "sound_capacity_bps": 9 * 512_000},
    }


@pytest.mark.parametrize(
    "name, want",
    [
        ("link_utilisation.catchup", 100.0 * 2 * 9 * 110 * BLOCK / (9 * 512_000 * 6.0)),
        ("head_of_line_wait_share.catchup", 25.0),
        ("slow_peer_block_share.catchup", 100.0 * 10 / 1500),
        ("slow_peer_ban_s.catchup", 1.1),
        ("blocks_per_window.catchup", 70.0),
    ],
)
def test_reader_on_a_made_record_on_nothing_and_on_another_generators(name, want):
    read = lookup.load_reader(name)
    assert read(made_record()) == pytest.approx(want)
    assert read({}) is None
    # tiny.catchup's record: join-loop's, with no links in it
    plain = made_record()
    del plain["links"]
    assert read(plain) is None
    # a program whose pool counts nothing and records no such span (the parent)
    parent = made_record()
    for j in parent["links"]["joins"]:
        j["pool"], j["rate_ban_s"] = None, None
    parent["links"]["head_wait_s"] = None
    if name != "blocks_per_window.catchup":
        assert read(parent) is None


def test_link_utilisation_is_not_clamped():
    rec = made_record()
    for j in rec["links"]["joins"]:
        j["fetch_s"] /= 10.0
    assert links.link_utilisation(rec) > 100.0  # a leak shows as one


# --- (f) the cell in BENCHMARK.json and the harness end to end -----------------------


def test_lookup_finds_the_cell_its_metrics_and_leaves_the_others_theirs():
    spec = lookup.load_spec()
    cell = lookup.load_cell(spec, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "val150-kvstore-links", "join-links",
    )
    assert len(cell["why"]) <= 200
    config, mix = cell["config_data"], cell["mix"]
    base = lookup.load_cell(spec, "val150.catchup")
    for key, value in base["config_data"].items():
        if key not in ("name", "source", "deployment", "guarantees", "assumed", "reduced"):
            assert config[key] == value, key  # the chain and the pins, byte for byte
    assert config["guarantees"][:3] == base["config_data"]["guarantees"]
    assert "never banned" in config["guarantees"][3]
    entry = lookup.by_name(spec["configs"], cell["config"], "configuration")
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert (config["peers"], config["slow_peers"], config["link_rtt_ms"]) == (10, 1, 100)
    assert config["min_recv_rate_bps"] == pool_mod.MIN_RECV_RATE
    assert config["slow_link_rate_bps"] < pool_mod.MIN_RECV_RATE < config["link_rate_bps"]
    assert callable(lookup.load_generator(mix["generator"]).Traffic)
    for k in ("join_timeout_s", "warm_joins", "trace_slice_dispatches", "trace_slice_max_s"):
        assert mix[k] == base["mix"][k], k
    assert mix["faults"] == ["half_batch", "verdict_altered", "link_unmetered", "floor_on_everyone"]
    assert [m["name"] for m in lookup.metrics_for(spec, CELL, "end_to_end")] == [
        "catchup_rate", "setup_s",
    ]
    per_layer = [m["name"] for m in lookup.metrics_for(spec, CELL, "per_layer")]
    assert per_layer[-5:] == list(NEW_METRICS)
    # everything val150.catchup reports, and the five
    assert per_layer[:-5] == [
        m["name"] for m in lookup.metrics_for(spec, "val150.catchup", "per_layer")
    ]
    links = [m for m in spec["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in links] == list(NEW_METRICS)
    for m in links:
        assert m["moves"] == "catchup_rate" and m["workloads"] == [CELL]
        assert m["layer"] in ("entry: blocksync/pool.py", "entry: blocksync/reactor.py")
    for other in (w["name"] for w in spec["workloads"] if w["name"] != CELL):
        names = [m["name"] for m in lookup.metrics_for(spec, other, "per_layer")]
        assert not [n for n in names if n in NEW_METRICS], other


def tiny_spec() -> dict:
    """benchmark/tests/tiny.py's spec with a third cell: the tiny links
    configuration under ``tiny-join-links``, listed wherever
    BENCHMARK.json lists the real cell (tiny.py maps that to
    tiny.catchup, whose mix is join-loop)."""
    spec = tiny.spec()
    spec["configs"].append(
        {"name": "tiny-kvstore-links", "source": "none", "reduced": [],
         "file": "benchmark/testdata/tiny-kvstore-links.json", "why": "tests"}
    )
    spec["workloads"].append(
        {"name": "tiny.catchup-delayed", "config": "tiny-kvstore-links",
         "traffic": "tiny-join-links", "chips": 1, "why": "tests"}
    )
    real = lookup.load_spec()
    for m, r in zip(
        spec["end_to_end"] + spec["per_layer"], real["end_to_end"] + real["per_layer"]
    ):
        if CELL in r.get("workloads", ()):
            m["workloads"] = [
                w for w, rw in zip(m["workloads"], r["workloads"]) if rw != CELL
            ] + ["tiny.catchup-delayed"]
    return spec


def tiny_config() -> dict:
    with open(os.path.join(lookup.HERE, "testdata", "tiny-kvstore-links.json")) as f:
        return json.load(f)


@pytest.fixture
def tiny_floor(monkeypatch):
    """The tiny links are a fiftieth of the deployment's, and so is the
    floor: the test sets the program's constant to the tiny
    configuration's, which the generator checks."""
    pad_min = ops_ed.PAD_MIN
    monkeypatch.setattr(pool_mod, "MIN_RECV_RATE", tiny_config()["min_recv_rate_bps"])
    yield
    ops_ed.PAD_MIN = pad_min  # the tiny configuration pins it


def drive(fault=None, seconds: float = 4.0) -> dict:
    undo = []
    known = {**faults.ALL, **join_links.FAULTS}

    def hook(traffic):
        if fault is not None:
            undo.append(known[fault](traffic))

    try:
        return run.execute(
            tiny_spec(), "tiny.catchup-delayed", SEED, seconds, False, tiny.DEVICE, fault=hook
        )
    finally:
        for u in undo:
            u()


def test_tiny_join_links_through_the_harness_reads_correct(tiny_floor):
    r = drive()
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"catchup_rate", "setup_s"}
    config = tiny_config()
    assert 0 < r["metrics"]["catchup_rate"]["value"] < (
        reference_links.link_ceiling_blocks_per_s(config)
    )
    assert set(r["compared"]) >= {
        "sound_peers_banned", "link_overrun_bytes", "responses_before_rtt",
        "rate_over_link_ceiling", "blocks_from_unknown_peer", "sigs_unverified",
    }
    assert all(v == {"value": 0.0, "limit": 0.0} for v in r["compared"].values())


@pytest.mark.parametrize(
    "fault, trips",
    [
        ("half_batch", "sigs_unverified"),
        ("verdict_altered", "joins_finished_min1"),
        ("link_unmetered", "link_overrun_bytes"),
        ("floor_on_everyone", "sound_peers_banned"),
    ],
)
def test_faults_are_not_correct_under_join_links(fault, trips, tiny_floor):
    r = drive(fault)
    assert r["correct"] is False, r["compared"]
    assert r["compared"][trips]["value"] > 0, r["compared"]


def test_the_generator_fails_fast_on_a_floor_or_a_block_size_it_was_not_told(tiny_floor, monkeypatch):
    mix = lookup.load_cell(tiny_spec(), "tiny.catchup-delayed")["mix"]
    with pytest.raises(RuntimeError, match="block_wire_bytes"):
        join_links.Traffic(dict(tiny_config(), block_wire_bytes=1000), mix, SEED)
    monkeypatch.setattr(pool_mod, "MIN_RECV_RATE", 131_072)
    with pytest.raises(RuntimeError, match="floor"):
        join_links.Traffic(tiny_config(), mix, SEED)


def test_a_tiny_join_repeats_for_a_seed_bans_its_slow_peer_and_says_so(tiny_floor):
    """One join outside the harness: the traffic is a function of the
    seed, the slow peer is banned for its rate and for nothing else,
    its requests are taken back, and the record holds what the five
    readers read."""
    cell = lookup.load_cell(tiny_spec(), "tiny.catchup-delayed")
    config, mix = cell["config_data"], cell["mix"]
    t = join_links.Traffic(config, mix, SEED)
    again = join_links.Traffic(config, mix, SEED)
    other = join_links.Traffic(config, mix, SEED + 1)
    tip = lambda x: x.src.block_store.load_block(x.limit).hash()  # noqa: E731
    assert tip(t) == tip(again) != tip(other)
    assert [t._links()[1] for _ in range(6)] == [again._links()[1] for _ in range(6)]
    assert [again._links()[1] for _ in range(6)] != [other._links()[1] for _ in range(12)][6:]
    import time

    j = asyncio.run(t._join(time.perf_counter() + 60))
    assert j["caught_up"] and j["blocks_applied"] == config["chain_blocks"] - 1
    (slow,) = reference_links.under_floor(config, j["rates"])
    assert j["banned"] == [slow]
    stats = j["pool"]["peers"]
    assert stats[slow]["banned"][0] == "rate" and stats[slow]["redone"] >= 8
    assert all(s["banned"] is None and s["timeouts"] == 0 for p, s in stats.items() if p != slow)
    assert sum(s["blocks"] for s in stats.values()) >= config["chain_blocks"]
    bans = [e for e in j["tracer"].snapshot() if e["name"] == join_links.BAN]
    assert len(bans) == 1
    args = bans[0]["args"]
    assert (args["peer"], args["reason"], args["redone"]) == (slow, "rate", stats[slow]["redone"])
    assert 0 < args["rate_bps"] < config["min_recv_rate_bps"] and args["pending"] == args["redone"]
    assert bans[0]["dur_ns"] >= 1e9 * pool_mod.RATE_EVIDENCE_S
    links_rec = t._reduce_links([j])
    row = links_rec["joins"][0]
    assert row["slow"] == [slow] and 1.0 <= row["rate_ban_s"] <= 3.0
    assert links_rec["head_wait_s"] > 0.5  # the head sat at the slow peer for a second
    rec = {"window_s": 4.0, "links": links_rec, "spans": [
        {"name": e["name"], "dur_s": e["dur_ns"] / 1e9, "jobs": e["args"].get("jobs")}
        for e in j["tracer"].snapshot() if e["ph"] == "X"
    ]}
    values = {n: lookup.load_reader(n)(rec) for n in NEW_METRICS}
    assert all(v is not None for v in values.values()), values
    assert 20.0 <= values["link_utilisation.catchup"] <= 100.0
    assert values["slow_peer_block_share.catchup"] < 5.0
    assert 1.0 <= values["blocks_per_window.catchup"] <= 63.0
    t.free()
