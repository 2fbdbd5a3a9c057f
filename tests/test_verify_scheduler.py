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

import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import parallel_verify as pv
from cometbft_tpu.crypto import scheduler as sched_mod
from cometbft_tpu.crypto.batch import CpuBatchVerifier
from cometbft_tpu.crypto.keys import Ed25519PrivKey, Secp256k1PrivKey
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
