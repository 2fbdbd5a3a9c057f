"""Measured host/device dispatch crossover (VERDICT r2 weak #3: a
static _MIN_TPU_BATCH routed 150-sig commits to a 98ms device dispatch
that costs 12ms on host). The calibrator learns both costs from
observed walls and routes each batch to whichever path is predicted
faster; set_min_tpu_batch(1) still forces the device (dryrun/tests)."""

import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import scheduler as crypto_sched
from cometbft_tpu.crypto.batch import CpuBatchVerifier, _Calibration
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.utils import device


@pytest.fixture
def routing():
    """Process-wide routing state, restored after the test."""
    old = crypto_batch.default_backend()
    old_min = crypto_batch._MIN_TPU_BATCH
    yield
    crypto_batch.set_min_tpu_batch(old_min)
    crypto_batch.set_default_backend(old)


def _fake_kernel(monkeypatch, ready_s=0.0):
    """ops/ed25519.verify_batch_async replaced by a handle that is
    ready after ``ready_s`` and accepts every lane; returns the list
    of dispatch sizes it saw."""
    import time

    from cometbft_tpu.ops import ed25519 as ed

    sizes = []

    class FakeHandle:
        def __init__(self, n):
            self.n = n

        def wait(self):
            time.sleep(ready_s)  # the watcher blocks on readiness
            return self

        def result(self):
            return [True] * self.n

    def fake_verify_batch_async(items):
        sizes.append(len(items))
        return FakeHandle(len(items))

    monkeypatch.setattr(ed, "verify_batch_async", fake_verify_batch_async)
    return sizes


def _signed(n, tag):
    privs = [Ed25519PrivKey.generate() for _ in range(n)]
    return [
        (p.pub_key(), b"%s|%d" % (tag, i), p.sign(b"%s|%d" % (tag, i)))
        for i, p in enumerate(privs)
    ]


def test_high_flat_cost_moves_crossover_past_commit_sizes():
    c = _Calibration()
    # two post-compile dispatches with a high flat cost (~90-100 ms:
    # what a dispatch padded to a 32,768-lane bucket costs on a v5e)
    c.observe_device(4800, 0.105)
    c.observe_device(4800, 0.095)
    c.observe_host(150, 150 * 80e-6)
    assert not c.device_wins(150), "150-sig commit must stay on host"
    assert not c.device_wins(64)
    assert c.device_wins(4800), "replay windows must still dispatch"
    assert 500 < c.crossover() < 3000


def test_local_chip_flat_cost_keeps_vote_waves_on_device():
    c = _Calibration()
    c.observe_device(256, 0.004)  # ~3ms flat local chip
    c.observe_device(256, 0.0045)
    c.observe_host(150, 150 * 80e-6)
    assert c.device_wins(150), "local chip should win a 150-sig wave"
    assert c.crossover() < 100


def test_empty_and_non_positive_samples_never_enter_the_ewma():
    c = _Calibration()
    flat0 = c.flat_s
    c.observe_device(0, 0.004)
    c.observe_device(150, 0.0)
    c.observe_device(150, -1.0)
    assert c.flat_s == flat0 and c.device_samples == 0
    c.observe_device(150, 0.004)  # a genuine dispatch wall
    assert c.device_samples == 1


def test_compile_walls_never_poison_the_ewma():
    c = _Calibration()
    flat0 = c.flat_s
    c.observe_device(4800, 180.0)  # first-call XLA compile
    assert c.flat_s == flat0 and c.device_samples == 0


def test_routing_uses_calibration(monkeypatch, routing):
    # host-favored calibration: a 100-sig ticket must route to host even
    # on the tpu backend, without touching the device at all
    monkeypatch.setattr(
        crypto_batch, "calibration", _Calibration()
    )
    crypto_batch.calibration.observe_device(4800, 0.1)
    crypto_batch.calibration.observe_device(4800, 0.1)

    from cometbft_tpu.ops import ed25519 as ed

    def boom(items):  # pragma: no cover - must never be reached
        raise AssertionError("a host-routed ticket touched the device")

    monkeypatch.setattr(ed, "verify_batch_async", boom)
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(64)
    items = _signed(100, b"route")
    ref = CpuBatchVerifier()
    for pk, m, sig in items:
        ref.add(pk, m, sig)
    ticket = crypto_sched.scheduler().submit(items, label="route")
    assert ticket.result(timeout=60) == ref.verify() == (True, [True] * 100)
    assert ticket.backend == "tpu"
    assert crypto_batch.LAST_ROUTE["path"] == "host"
    assert crypto_batch.LAST_ROUTE["n"] == 100
    assert crypto_batch.LAST_ROUTE["crossover"] > 100


def test_force_min_batch_1_bypasses_calibration(monkeypatch, routing):
    """The dryrun/test force-switch must still reach the device path
    regardless of what calibration thinks (here: fake the kernel)."""
    monkeypatch.setattr(crypto_batch, "calibration", _Calibration())
    crypto_batch.calibration.observe_device(4800, 0.5)  # device looks awful

    sizes = _fake_kernel(monkeypatch)
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(1)
    ticket = crypto_sched.scheduler().submit(_signed(1, b"m"))
    ok, _ = ticket.result(timeout=60)
    assert ok and sizes == [1]
    assert ticket.backend == "tpu"
    assert crypto_batch.LAST_ROUTE["path"] == "device"
    assert crypto_batch.LAST_ROUTE["crossover"] is None


def test_exploration_heals_poisoned_flat_cost():
    """A 1-10s recompile wall that slips past the first-sample filter
    inflates flat_s; periodic exploration must route a batch to the
    device anyway so a healthy sample can pull the estimate back."""
    c = _Calibration()
    c.observe_device(4800, 0.1)       # healthy first sample
    c.observe_device(4800, 3.0)       # per-shape recompile slips in
    assert not c.device_wins(4800), "poisoned estimate routes host"
    # every EXPLORE_EVERY'th eligible host-routed batch explores
    explored = [c.should_explore() for _ in range(c.EXPLORE_EVERY)]
    assert explored.count(True) == 1 and explored[-1] is True
    # each explored dispatch lands a healthy wall; the EWMA (alpha
    # 0.4) converges back within a handful of explore cycles
    cycles = 0
    while not c.device_wins(4800):
        cycles += 1
        assert cycles <= 10, "exploration failed to heal the estimate"
        while not c.should_explore():
            pass
        c.observe_device(4800, 0.11)
    assert 1 <= cycles <= 10
    # device traffic resets the streak
    c.note_device_used()
    assert not c.should_explore()


def test_async_seam_feeds_calibration(monkeypatch, routing):
    """BENCH_r05 first run: commit150's auto leg routed a 150-sig
    commit to the device at 10x the host wall — the async seam (the
    one verify_commit_light actually takes) never fed the EWMA, so
    the optimistic flat-cost seed was never corrected. The scheduler's
    readiness watcher must observe the dispatch wall."""
    monkeypatch.setattr(crypto_batch, "calibration", _Calibration())
    cal = crypto_batch.calibration

    _fake_kernel(monkeypatch, ready_s=0.002)
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(1)  # force the device route
    ticket = crypto_sched.scheduler().submit(_signed(150, b"async"))
    ok, verdicts = ticket.result(timeout=60)
    assert ok and len(verdicts) == 150
    # the watcher observes before it resolves the ticket
    assert cal.device_samples == 1, (
        "readiness watcher never fed the device EWMA"
    )


def test_result_time_overlap_does_not_poison_flat_cost(monkeypatch, routing):
    """The watcher observes READINESS, not result() latency: a caller
    that sits on the ticket for seconds of host work (the replay
    pipeline) must not inflate the EWMA and flip bulk windows to
    host."""
    import time

    monkeypatch.setattr(crypto_batch, "calibration", _Calibration())
    cal = crypto_batch.calibration

    _fake_kernel(monkeypatch, ready_s=0.002)  # ready ~instantly
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(1)
    ticket = crypto_sched.scheduler().submit(_signed(150, b"late"))
    deadline = time.time() + 2.0
    while cal.device_samples == 0 and time.time() < deadline:
        time.sleep(0.005)
    assert cal.device_samples == 1
    flat_after_ready = cal.flat_s
    time.sleep(0.2)  # caller overlaps host work before resolving
    ticket.result(timeout=60)
    assert cal.device_samples == 1, "result() must not re-observe"
    assert cal.flat_s == flat_after_ready, (
        "overlapped resolution leaked into the EWMA"
    )
    assert cal.flat_s < 0.05, "the 0.2 s of overlap entered the EWMA"


# --- the one routing decision, as a table ---------------------------------

# calibrations: the device wins every size from 1 up / loses every size
_DEVICE = dict(flat_s=1e-6, lane_s=1e-6, host_s=80e-6)
_HOST = dict(flat_s=10.0, lane_s=1e-6, host_s=80e-6)

# (backend, floor, lanes, devices, calibration, platform is the CPU)
#   -> (path, ticket.backend, degraded)
_DECISIONS = [
    # tpu: the floor first, then the calibration, the platform last
    ("tpu", 64, 63, 1, _DEVICE, False, "host", "tpu", False),
    ("tpu", 64, 64, 1, _DEVICE, False, "device", "tpu", False),
    ("tpu", 64, 4800, 1, _DEVICE, False, "device", "tpu", False),
    ("tpu", 64, 4800, 8, _DEVICE, False, "device", "tpu", False),
    ("tpu", 64, 4800, 1, _HOST, False, "host", "tpu", False),
    ("tpu", 64, 4800, 1, _DEVICE, True, "host", "tpu", False),
    # forced (floor 1): neither calibration nor platform is asked
    ("tpu", 1, 1, 1, _HOST, True, "device", "tpu", False),
    ("tpu", 1, 0, 1, _DEVICE, False, "host", "tpu", False),
    # mesh: no calibration gate and no platform probe; the floor holds
    ("mesh", 64, 63, 8, _DEVICE, False, "host", "mesh", False),
    ("mesh", 64, 64, 8, _HOST, True, "device", "mesh", False),
    ("mesh", 64, 4800, 8, _HOST, False, "device", "mesh", False),
    ("mesh", 1, 1, 8, _HOST, True, "device", "mesh", False),
    ("mesh", 1, 0, 8, _HOST, True, "host", "mesh", False),
    # mesh without a mesh: the host, degraded, whatever the size
    ("mesh", 64, 4800, 1, _DEVICE, False, "host", "mesh-degraded", True),
    ("mesh", 1, 4800, 1, _DEVICE, False, "host", "mesh-degraded", True),
    # the host backends never go to the device, forced or not
    ("cpu", 64, 4800, 8, _DEVICE, False, "host", "cpu", False),
    ("cpu", 1, 4800, 1, _DEVICE, False, "host", "cpu", False),
    ("cpu-parallel", 64, 4800, 8, _DEVICE, False, "host", "cpu-parallel", False),
    ("cpu-parallel", 1, 63, 1, _DEVICE, False, "host", "cpu-parallel", False),
]


@pytest.mark.parametrize(
    "backend,floor,lanes,devices,cal,on_cpu,path,label,degraded",
    _DECISIONS,
    ids=[
        f"{d[0]}-floor{d[1]}-n{d[2]}-dev{d[3]}-"
        f"{'devwins' if d[4] is _DEVICE else 'hostwins'}-"
        f"{'cpu' if d[5] else 'chip'}"
        for d in _DECISIONS
    ],
)
def test_decision_table(
    monkeypatch, routing,
    backend, floor, lanes, devices, cal, on_cpu, path, label, degraded,
):
    c = _Calibration()
    c.flat_s, c.lane_s, c.host_s = cal["flat_s"], cal["lane_s"], cal["host_s"]
    monkeypatch.setattr(crypto_batch, "calibration", c)
    monkeypatch.setattr(
        device, "backend",
        lambda: device.Backend(
            "cpu" if on_cpu else "tpu", "test", devices
        ),
    )
    crypto_batch.set_default_backend(backend)
    crypto_batch.set_min_tpu_batch(floor)
    assert crypto_batch.decide(lanes) == (path, label, degraded)
    forced = floor <= 1
    assert crypto_batch.LAST_ROUTE == {
        "path": path,
        "n": lanes,
        "crossover": None if forced else c.crossover(),
    }


def test_decision_explores_after_a_streak_of_host_routes(
    monkeypatch, routing
):
    """The calibration says host: every EXPLORE_EVERY'th eligible
    ticket goes to the device anyway; a ticket under the floor neither
    explores nor counts toward the streak."""
    c = _Calibration()
    c.flat_s = 10.0
    monkeypatch.setattr(crypto_batch, "calibration", c)
    monkeypatch.setattr(
        device, "backend", lambda: device.Backend("tpu", "test", 1)
    )
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(64)
    paths = []
    for _ in range(c.EXPLORE_EVERY):
        assert crypto_batch.decide(8)[0] == "host"
        paths.append(crypto_batch.decide(4800)[0])
    assert paths.count("device") == 1 and paths[-1] == "device"


def test_set_default_backend_refuses_an_unknown_name(routing):
    before = crypto_batch.default_backend()
    with pytest.raises(AssertionError):
        crypto_batch.set_default_backend("unit-test-backend")
    assert crypto_batch.default_backend() == before
    assert crypto_batch.BACKENDS == ("tpu", "cpu", "cpu-parallel", "mesh")


def test_crypto_section_has_one_knob_and_retired_keys_load_as_unknown(tmp_path):
    """``[crypto]`` is ``batch_backend`` alone since PR 32; a
    config.toml that still carries the three retired keys loads as
    any unknown key does: skipped in silence, nothing set."""
    import dataclasses

    from cometbft_tpu.config.config import (
        CryptoConfig,
        default_config,
        load_toml,
        write_toml,
    )

    assert [f.name for f in dataclasses.fields(CryptoConfig)] == [
        "batch_backend"
    ]
    path = str(tmp_path / "config" / "config.toml")
    write_toml(default_config(str(tmp_path)), path)
    text = open(path).read()
    assert '[crypto]\nbatch_backend = ""\n' in text
    with open(path, "w") as f:
        f.write(text.replace(
            '[crypto]\nbatch_backend = ""\n',
            '[crypto]\nbatch_backend = "mesh"\nmin_batch_for_tpu = 2\n'
            "coalesce_window_ms = 2.0\nmax_lanes = 131072\n"
            "no_such_key = 1\n",
        ))
    cfg = load_toml(path)
    assert cfg.crypto == CryptoConfig(batch_backend="mesh")
    for name in ("min_batch_for_tpu", "coalesce_window_ms", "max_lanes",
                 "no_such_key"):
        assert not hasattr(cfg.crypto, name)
