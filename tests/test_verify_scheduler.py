"""Unified verify scheduler (crypto/scheduler.py): serial-equivalent
verdicts, priority ordering at chunk granularity, the aging/promotion
starvation guard, and the mesh backend's route/degrade ladder.

Device dispatches are exercised against a FAKE ops.ed25519 handle —
the real sharded kernel is differential-tested in
test_ed25519_verify.py / test_sharded_verify.py; here the contract
under test is the scheduler's routing, merging, and degrade paths.
"""

import threading
import time

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import parallel_verify as pv
from cometbft_tpu.crypto import scheduler as sched_mod
from cometbft_tpu.crypto.batch import CpuBatchVerifier
from cometbft_tpu.crypto.keys import Ed25519PrivKey, Secp256k1PrivKey
from cometbft_tpu.crypto.lanes import LaneBatch
from cometbft_tpu.crypto.scheduler import (
    PRIORITY_CATCHUP,
    PRIORITY_LIGHT,
    PRIORITY_LIVE,
    VerifyScheduler,
    VerifyTicket,
)
from cometbft_tpu.utils import device

# key generation dominates test wall time: a small reusable pool is
# plenty (verdicts depend on (msg, sig), not key uniqueness)
_ED_KEYS = [Ed25519PrivKey.generate() for _ in range(8)]
_SECP_KEYS = [Secp256k1PrivKey.generate() for _ in range(2)]


def make_items(n, bad=(), mixed=False):
    items = []
    for i in range(n):
        if mixed and i % 5 == 4:
            sk = _SECP_KEYS[i % len(_SECP_KEYS)]
        else:
            sk = _ED_KEYS[i % len(_ED_KEYS)]
        msg = b"sched-lane-%d" % i
        sig = sk.sign(msg)
        if i in bad:
            sig = b"\x00" * len(sig)
        items.append((sk.pub_key(), msg, sig))
    return items


def serial_verdicts(items):
    v = CpuBatchVerifier()
    for pk, msg, sig in items:
        v.add(pk, msg, sig)
    return v.verify()


@pytest.fixture
def sched():
    s = VerifyScheduler()
    yield s
    s.close()


@pytest.fixture
def cpu_backend():
    old = crypto_batch.default_backend()
    crypto_batch.set_default_backend("cpu")
    yield
    crypto_batch.set_default_backend(old)


@pytest.fixture
def restore_routing():
    old_backend = crypto_batch.default_backend()
    old_floor = crypto_batch._MIN_TPU_BATCH
    yield
    crypto_batch.set_default_backend(old_backend)
    crypto_batch.set_min_tpu_batch(old_floor)


def set_device_count(monkeypatch, n):
    """The routing decision reads the device count from
    utils/device.backend(): give it ``n`` devices."""
    monkeypatch.setattr(
        device, "backend", lambda: device.Backend("cpu", "cpu", n)
    )


class FakeDeviceHandle:
    """Stands in for ops.ed25519.AsyncVerdicts: verdicts computed by
    the same per-key host math the scheduler falls back to."""

    def __init__(self, ed_items):
        from cometbft_tpu.crypto.keys import Ed25519PubKey

        if isinstance(ed_items, LaneBatch):  # the lanes by columns
            ed_items = [
                (m, k.tobytes(), s.tobytes())
                for m, k, s in zip(ed_items.msgs, ed_items.keys, ed_items.sigs)
            ]
        self.verdicts = [
            Ed25519PubKey(pk).verify(msg, sig)
            for msg, pk, sig in ed_items
        ]

    def wait(self):
        return self

    def result(self):
        return self.verdicts


# --- verdict parity ------------------------------------------------------


def test_serial_equivalence_differential(sched, cpu_backend):
    items = make_items(40, bad={3, 17, 39}, mixed=True)
    want_all, want = serial_verdicts(items)
    ticket = sched.submit(items, priority=PRIORITY_LIVE, label="diff")
    got_all, got = ticket.result(timeout=60)
    assert got == want
    assert got_all == want_all
    assert ticket.backend == "cpu"
    assert ticket.wall() is not None and ticket.wall() >= 0


def test_empty_submit_matches_batch_verifier(sched, cpu_backend):
    # CpuBatchVerifier.verify() on zero lanes is (False, []); an empty
    # ticket must resolve immediately with the same shape
    t = sched.submit([], priority=PRIORITY_LIGHT)
    assert t.done()
    assert t.result(timeout=1) == (False, [])


def test_all_classes_same_verdicts(sched, cpu_backend):
    items = make_items(12, bad={5})
    want = serial_verdicts(items)
    tickets = [
        sched.submit(items, priority=p, label=f"cls-{p}")
        for p in (PRIORITY_LIVE, PRIORITY_LIGHT, PRIORITY_CATCHUP)
    ]
    for t in tickets:
        assert t.result(timeout=60) == want


def test_priority_clamped(sched, cpu_backend):
    items = make_items(2)
    t = sched.submit(items, priority=99)
    assert t.priority == PRIORITY_CATCHUP
    t.result(timeout=30)
    t2 = sched.submit(items, priority=-5)
    assert t2.priority == PRIORITY_LIVE
    t2.result(timeout=30)
    t3 = sched.submit(items, priority=None)
    assert t3.priority == PRIORITY_CATCHUP
    t3.result(timeout=30)


# --- priority ordering / starvation guard --------------------------------


def _slow_chunks(monkeypatch, delay):
    """Make host chunks take a visible wall so ordering is observable,
    and force small chunks so every ticket splits into several."""
    real = pv._verify_chunk

    def slow(items, tier):
        time.sleep(delay)
        return real(items, tier)

    monkeypatch.setattr(pv, "_verify_chunk", slow)
    monkeypatch.setattr(
        pv.engine(), "chunk_size", lambda n: 4, raising=False
    )


def test_live_preempts_catchup_at_chunk_boundary(
    sched, cpu_backend, monkeypatch
):
    _slow_chunks(monkeypatch, 0.01)
    catchup_items = make_items(32)
    live_items = make_items(8)
    t_catchup = sched.submit(
        catchup_items, priority=PRIORITY_CATCHUP, label="storm"
    )
    # let the storm route and start chunking before the live wave lands
    time.sleep(0.02)
    t_live = sched.submit(live_items, priority=PRIORITY_LIVE, label="live")
    assert t_live.result(timeout=30) == serial_verdicts(live_items)
    assert t_catchup.result(timeout=30) == serial_verdicts(catchup_items)
    # live arrived mid-storm yet finished first: preemption happened
    # at a chunk boundary, not behind the storm's full residue
    assert t_live.t_done < t_catchup.t_done


def test_aging_promotion_unit():
    """_pick_locked serves an aged lower-class ticket once every
    promote_every picks — deterministic, no dispatcher involved."""
    s = VerifyScheduler(promote_after_s=0.0, promote_every=2)
    live = VerifyTicket([None] * 2, PRIORITY_LIVE, "live")
    old = VerifyTicket([None] * 2, PRIORITY_CATCHUP, "old")
    old.t_submit -= 1.0  # aged well past promote_after_s
    s._queues[PRIORITY_LIVE].append(live)
    s._queues[PRIORITY_CATCHUP].append(old)
    with s._cv:
        first = s._pick_locked()
        second = s._pick_locked()
    assert first is live  # credit accrues, threshold not yet met
    assert second is old  # every promote_every-th pick is the aged one
    assert s.promoted == 1


def test_catchup_completes_under_sustained_live_flood(
    cpu_backend, monkeypatch
):
    """The starvation-guard satellite: flood the live lane without a
    gap and assert a catch-up ticket still completes WHILE the flood
    is running, via aging promotion."""
    s = VerifyScheduler(promote_after_s=0.05, promote_every=2)
    _slow_chunks(monkeypatch, 0.002)
    stop = threading.Event()
    live_items = make_items(8)

    def flood():
        while not stop.is_set():
            s.submit(live_items, priority=PRIORITY_LIVE, label="flood")
            time.sleep(0.004)

    feeder = threading.Thread(target=flood, daemon=True)
    feeder.start()
    try:
        time.sleep(0.05)  # flood is established
        catchup = make_items(8, bad={1})
        t = s.submit(catchup, priority=PRIORITY_CATCHUP, label="starved")
        got = t.result(timeout=5.0)  # must resolve DURING the flood
        assert got == serial_verdicts(catchup)
        assert not stop.is_set()
        assert s.promoted >= 1
    finally:
        stop.set()
        feeder.join(timeout=5)
        assert s.drain(timeout=30)
        s.close()


# --- mesh backend --------------------------------------------------------


def test_mesh_route_dispatches_device(sched, restore_routing, monkeypatch):
    import cometbft_tpu.ops.ed25519 as ops_ed

    crypto_batch.set_default_backend("mesh")
    crypto_batch.set_min_tpu_batch(1)  # force past the batch floor
    set_device_count(monkeypatch, 8)
    dispatched = []

    def fake_async(ed_items):
        dispatched.append(len(ed_items))
        return FakeDeviceHandle(ed_items)

    monkeypatch.setattr(ops_ed, "verify_batch_async", fake_async)
    items = make_items(16, bad={7}, mixed=True)
    want = serial_verdicts(items)
    t = sched.submit(items, priority=PRIORITY_LIVE, label="mesh")
    assert t.result(timeout=30) == want
    assert t.backend == "mesh"
    assert dispatched == [sum(1 for pk, _, _ in items
                              if pk.type_ == "ed25519")]
    assert sched.device_dispatches == 1


def test_mesh_degrades_without_mesh(sched, restore_routing, monkeypatch):
    import cometbft_tpu.ops.ed25519 as ops_ed

    crypto_batch.set_default_backend("mesh")
    crypto_batch.set_min_tpu_batch(1)
    set_device_count(monkeypatch, 1)

    def boom(ed_items):  # pragma: no cover - must never be reached
        raise AssertionError("degraded route must not touch the device")

    monkeypatch.setattr(ops_ed, "verify_batch_async", boom)
    items = make_items(12, bad={4})
    want = serial_verdicts(items)
    t = sched.submit(items, priority=PRIORITY_CATCHUP, label="degrade")
    assert t.result(timeout=30) == want
    assert t.backend == "mesh-degraded"
    assert sched.degraded == 1
    assert sched.device_dispatches == 0


def test_mesh_degrades_on_dispatch_failure(
    sched, restore_routing, monkeypatch
):
    """The device dispatch itself failing must fall through to host
    chunks — degraded and visible, never wedged."""
    import cometbft_tpu.ops.ed25519 as ops_ed

    crypto_batch.set_default_backend("mesh")
    crypto_batch.set_min_tpu_batch(1)
    set_device_count(monkeypatch, 8)

    def boom(ed_items):
        raise RuntimeError("no XLA for you")

    monkeypatch.setattr(ops_ed, "verify_batch_async", boom)
    items = make_items(10, bad={0})
    want = serial_verdicts(items)
    t = sched.submit(items, priority=PRIORITY_LIVE)
    assert t.result(timeout=30) == want
    assert t.backend == "mesh-degraded"
    assert sched.degraded == 1


def test_mesh_below_the_floor_stays_on_the_host(
    sched, restore_routing, monkeypatch
):
    """A mesh exists, but the ticket is under the batch floor: it
    verifies on the host plane with CpuBatchVerifier's verdicts, not
    degraded, and the device is never touched."""
    import cometbft_tpu.ops.ed25519 as ops_ed

    crypto_batch.set_default_backend("mesh")
    crypto_batch.set_min_tpu_batch(64)
    set_device_count(monkeypatch, 8)

    def boom(ed_items):  # pragma: no cover - must never be reached
        raise AssertionError("a ticket under the floor went to the device")

    monkeypatch.setattr(ops_ed, "verify_batch_async", boom)
    items = make_items(8, bad={2}, mixed=True)
    want = serial_verdicts(items)
    t = sched.submit(items, priority=PRIORITY_LIVE, label="small")
    assert t.result(timeout=30) == want
    assert t.backend == "mesh"
    assert crypto_batch.LAST_ROUTE["path"] == "host"
    assert crypto_batch.LAST_ROUTE["n"] == sum(
        1 for pk, _, _ in items if pk.type_ == "ed25519"
    )
    st = sched.stats()
    assert st["degraded"] == 0 and st["device_dispatches"] == 0
    assert st["host_chunks"] >= 1


# --- columnar tickets ----------------------------------------------------


def as_columns(items):
    """The ed25519 lanes ``items`` as the verify seam hands them on."""
    return LaneBatch(
        [msg for _, msg, _ in items],
        np.frombuffer(
            b"".join(pk.key_bytes for pk, _, _ in items), np.uint8
        ).reshape(-1, 32),
        np.frombuffer(
            b"".join(sig for _, _, sig in items), np.uint8
        ).reshape(-1, 64),
    )


@pytest.mark.parametrize(
    "route", ["host", "device", "device-dispatch-fails", "device-resolve-fails"]
)
def test_columnar_and_tuple_tickets_give_the_same_oks(
    route, sched, restore_routing, monkeypatch
):
    """The same lanes by columns and as tuples: the same verdicts in
    submission order on the host plane, on the device and down both
    degrade paths; a columnar ticket's are a bool array (indexable,
    listable), it is counted, and class and depth are as a tuple
    ticket's."""
    import cometbft_tpu.ops.ed25519 as ops_ed

    if route == "host":
        crypto_batch.set_default_backend("cpu")
    else:
        crypto_batch.set_default_backend("mesh")
        crypto_batch.set_min_tpu_batch(1)
        set_device_count(monkeypatch, 8)
    handed = []

    class Unreadable(FakeDeviceHandle):
        def result(self):
            raise RuntimeError("verdicts lost")

    def fake_async(ed_items):
        handed.append(ed_items)
        if route == "device-dispatch-fails":
            raise RuntimeError("no XLA for you")
        handle = Unreadable if route == "device-resolve-fails" else FakeDeviceHandle
        return handle(ed_items)

    monkeypatch.setattr(ops_ed, "verify_batch_async", fake_async)
    items = make_items(23, bad={0, 9, 22})
    want_all, want = serial_verdicts(items)
    batch = as_columns(items)
    with sched._cv:  # both queued before the dispatcher pops either
        a = sched.submit(items, priority=PRIORITY_LIGHT, label="tuples")
        b = sched.submit(batch, priority=PRIORITY_LIGHT, label="columns")
    assert (a.depth_ahead, b.depth_ahead) == (0, 23)
    assert a.priority == b.priority == PRIORITY_LIGHT
    got_all, got = a.result(timeout=60)
    col_all, col = b.result(timeout=60)
    assert (got_all, got) == (want_all, want) and type(got) is list
    assert type(col) is np.ndarray and col.dtype == bool
    assert col_all is want_all and list(col) == want == col.tolist()
    assert [bool(col[i]) for i in range(len(col))] == want
    assert b.items is batch and not a.columnar and b.columnar
    assert a.backend == b.backend
    st = sched.stats()
    assert st["columnar_tickets"] == 1 and st["tickets"] == 2
    assert st["lanes"] == 46
    if route == "host":
        assert handed == []
    else:
        # the tuple ticket's lanes as (msg, key_bytes, sig); the
        # columnar ticket's batch as it was handed in
        assert type(handed[0]) is list and handed[1] is batch
        assert st["degraded"] == (2 if route == "device-dispatch-fails" else 0)


def test_columnar_ticket_is_not_split_by_curve(sched, cpu_backend, monkeypatch):
    """``_plan`` on a columnar ticket: every lane ed25519 by
    construction, no pass over them (a tuple ticket's other curves
    still verify inline)."""
    seen = []
    real = crypto_batch.decide
    monkeypatch.setattr(
        crypto_batch, "decide", lambda n: seen.append(n) or real(n)
    )
    mixed = make_items(10, mixed=True)
    ed_only = [it for it in mixed if it[0].type_ == "ed25519"]
    t = VerifyTicket(as_columns(ed_only), PRIORITY_CATCHUP, "")
    path, ed_idx, ed_items = sched._plan(t)
    assert ed_idx == range(8) and ed_items is t.items
    t = VerifyTicket(mixed, PRIORITY_CATCHUP, "")
    path, ed_idx, ed_items = sched._plan(t)
    assert ed_idx == [0, 1, 2, 3, 5, 6, 7, 8]
    assert ed_items == [(m, pk.key_bytes, s) for pk, m, s in ed_only]
    assert t.oks[[4, 9]].all()  # the other curve, verified inline
    assert seen == [8, 8]


def test_a_commit_gains_no_attribute_from_a_coalesced_verify(cpu_backend):
    """Through the real scheduler, by columns: what the seam saves it
    saves on the first visit of a Commit (a node sees one once)."""
    import dataclasses

    from cometbft_tpu import types as T
    from cometbft_tpu.types import validation
    from cometbft_tpu.node.inprocess import make_genesis
    from cometbft_tpu.utils.chaingen import make_chain

    gen, pvs = make_genesis(4, chain_id="sched-columns")
    src = make_chain(gen, [pv.priv_key for pv in pvs], 4)
    try:
        vs, store = gen.validator_set(), src.block_store
        jobs = [
            (vs, store.load_block_meta(h).block_id, h, store.load_seen_commit(h))
            for h in range(1, 4)
        ]
        before = sched_mod.scheduler().stats()["columnar_tickets"]
        for _ in range(2):
            errors = validation.verify_commits_coalesced(gen.chain_id, jobs)
            assert errors == [None] * 3
        stats = sched_mod.scheduler().stats()
        assert stats["columnar_tickets"] == before + 2
        fields = {f.name for f in dataclasses.fields(T.Commit)}
        for _, _, _, commit in jobs:
            assert set(vars(commit)) - fields <= {"_sb_parts", "_raw_bytes"}
            for cs in commit.signatures:
                assert set(vars(cs)) == {
                    f.name for f in dataclasses.fields(cs)
                }
    finally:
        src.close_stores()


# --- observability -------------------------------------------------------


def test_queue_stats_shape(sched, cpu_backend):
    items = make_items(6)
    sched.submit(items, priority=PRIORITY_LIVE).result(timeout=30)
    sched.submit(items, priority=PRIORITY_CATCHUP).result(timeout=30)
    st = sched.queue_stats()
    for key in (
        "depth",
        "high_watermark",
        "enqueued",
        "dropped",
        "inflight_chunks",
        "promoted",
        "device_dispatches",
        "host_chunks",
        "degraded",
        "live_depth",
        "light_depth",
        "catchup_depth",
    ):
        assert key in st, key
    assert st["depth"] == 0
    assert st["enqueued"] == 12
    assert st["high_watermark"] >= 6


def test_dispatch_span_emitted(sched, cpu_backend):
    from cometbft_tpu.trace import global_tracer

    tr = global_tracer()
    events = []
    was_enabled = tr.enabled

    def obs(name, dur_ns, args):
        if name == "crypto.sched.dispatch":
            events.append((dur_ns, dict(args or {})))

    tr.enabled = True
    tr.add_observer(obs)
    try:
        items = make_items(5, bad={1})
        sched.submit(items, priority=PRIORITY_LIGHT, label="span").result(
            timeout=30
        )
    finally:
        tr.remove_observer(obs)
        tr.enabled = was_enabled
    assert events, "no crypto.sched.dispatch span observed"
    args = events[-1][1]
    assert args.get("cls") == "light"
    assert args.get("backend") == "cpu"
    assert args.get("lanes") == 5


def test_verify_storm_action(cpu_backend):
    """The chaos verify_storm leg, net-free: three concurrent classes
    through the shared scheduler, verdict parity + live budget + a
    non-starved catch-up lane (the full-net slice runs in
    tools/chaos_smoke.sh)."""
    from cometbft_tpu.chaos.verify_storm import storm_for_chaos

    rec = storm_for_chaos(storm_s=0.4, live_budget_ms=2500.0)
    assert rec["parity_ok"]
    for name in ("live", "light", "catchup"):
        assert rec[name]["tickets"] > 0, name
    assert rec["live"]["p95_ms"] <= 2500.0


def test_verify_storm_schedulable():
    from cometbft_tpu.chaos import FaultEvent, FaultSchedule

    ev = FaultEvent("verify_storm", at_height=2, storm_s=0.5)
    sched = FaultSchedule([ev])
    again = FaultSchedule.from_json(sched.to_json())
    assert again.events[0].action == "verify_storm"
    assert again.events[0].storm_s == 0.5
    assert again.events[0].live_budget_ms == 2500.0


def test_sched_stats_if_running_registry_contract(cpu_backend):
    # never CREATES the scheduler...
    old = sched_mod._SCHED
    try:
        sched_mod._SCHED = None
        assert sched_mod.sched_stats_if_running() is None
        # ...but reports the live one's gauges
        s = VerifyScheduler()
        sched_mod._SCHED = s
        s.submit(make_items(3), priority=PRIORITY_LIVE).result(timeout=30)
        st = sched_mod.sched_stats_if_running()
        assert st is not None and st["enqueued"] == 3
        s.close()
    finally:
        sched_mod._SCHED = old
