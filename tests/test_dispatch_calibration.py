"""Measured host/device dispatch crossover (VERDICT r2 weak #3: a
static _MIN_TPU_BATCH routed 150-sig commits to a 98ms device dispatch
that costs 12ms on host). The calibrator learns both costs from
observed walls and routes each batch to whichever path is predicted
faster; set_min_tpu_batch(1) still forces the device (dryrun/tests)."""

import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto.batch import _Calibration
from cometbft_tpu.crypto.keys import Ed25519PrivKey


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


def test_routing_uses_calibration(monkeypatch):
    # host-favored calibration: a 100-sig batch must route to host even
    # on the tpu backend, without touching the device at all
    monkeypatch.setattr(
        crypto_batch, "calibration", _Calibration()
    )
    crypto_batch.calibration.observe_device(4800, 0.1)
    crypto_batch.calibration.observe_device(4800, 0.1)

    old = crypto_batch._default_backend
    old_min = crypto_batch._MIN_TPU_BATCH
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(64)
    try:
        v = crypto_batch.create_batch_verifier()
        privs = [Ed25519PrivKey.generate() for _ in range(100)]
        for i, p in enumerate(privs):
            m = b"route|%d" % i
            v.add(p.pub_key(), m, p.sign(m))
        ok, verdicts = v.verify()
        assert ok and all(verdicts)
        assert crypto_batch.LAST_ROUTE["path"] == "host"
        assert crypto_batch.LAST_ROUTE["n"] == 100
        assert crypto_batch.LAST_ROUTE["crossover"] > 100
    finally:
        crypto_batch.set_min_tpu_batch(old_min)
        crypto_batch.set_default_backend(old)


def test_force_min_batch_1_bypasses_calibration(monkeypatch):
    """The dryrun/test force-switch must still reach the device path
    regardless of what calibration thinks (here: fake the kernel)."""
    monkeypatch.setattr(crypto_batch, "calibration", _Calibration())
    crypto_batch.calibration.observe_device(4800, 0.5)  # device looks awful

    calls = {}

    def fake_verify_batch(items):
        calls["n"] = len(items)
        return [True] * len(items)

    from cometbft_tpu.ops import ed25519 as ed

    monkeypatch.setattr(ed, "verify_batch", fake_verify_batch)
    old = crypto_batch._default_backend
    old_min = crypto_batch._MIN_TPU_BATCH
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(1)
    try:
        v = crypto_batch.create_batch_verifier()
        p = Ed25519PrivKey.generate()
        v.add(p.pub_key(), b"m", p.sign(b"m"))
        ok, _ = v.verify()
        assert ok and calls["n"] == 1
        assert crypto_batch.LAST_ROUTE["path"] == "device"
    finally:
        crypto_batch.set_min_tpu_batch(old_min)
        crypto_batch.set_default_backend(old)


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


def test_async_seam_feeds_calibration(monkeypatch):
    """BENCH_r05 first run: commit150's auto leg routed a 150-sig
    commit to the device at 10x the host wall — the async seam (the
    one verify_commit_light actually takes) never fed the EWMA, so
    the optimistic flat-cost seed was never corrected. verify_async's
    readiness watcher must observe the dispatch wall."""
    import time

    monkeypatch.setattr(crypto_batch, "calibration", _Calibration())
    cal = crypto_batch.calibration

    class FakeHandle:
        def wait(self):
            time.sleep(0.002)  # the watcher blocks on readiness
            return self

        def result(self):
            return [True] * 150

    from cometbft_tpu.ops import ed25519 as ed

    monkeypatch.setattr(
        ed, "verify_batch_async", lambda items: FakeHandle()
    )
    old = crypto_batch._default_backend
    old_min = crypto_batch._MIN_TPU_BATCH
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(1)  # force the device route
    try:
        v = crypto_batch.create_batch_verifier()
        privs = [Ed25519PrivKey.generate() for _ in range(150)]
        for i, p in enumerate(privs):
            m = b"async|%d" % i
            v.add(p.pub_key(), m, p.sign(m))
        pending = v.verify_async()
        ok, verdicts = pending.result()
        assert ok and len(verdicts) == 150
        # the watcher thread races result(); poll briefly
        deadline = time.time() + 2.0
        while cal.device_samples == 0 and time.time() < deadline:
            time.sleep(0.005)
        assert cal.device_samples == 1, (
            "readiness watcher never fed the device EWMA"
        )
    finally:
        crypto_batch.set_min_tpu_batch(old_min)
        crypto_batch.set_default_backend(old)


def test_result_time_overlap_does_not_poison_flat_cost(monkeypatch):
    """The watcher observes READINESS, not result() latency: a caller
    that sits on the handle for seconds of host work (the replay
    pipeline) must not inflate the EWMA and flip bulk windows to
    host."""
    import time

    monkeypatch.setattr(crypto_batch, "calibration", _Calibration())
    cal = crypto_batch.calibration

    class FakeHandle:
        def wait(self):
            time.sleep(0.002)  # device ready ~instantly
            return self

        def result(self):
            return [True] * 150

    from cometbft_tpu.ops import ed25519 as ed

    monkeypatch.setattr(
        ed, "verify_batch_async", lambda items: FakeHandle()
    )
    old = crypto_batch._default_backend
    old_min = crypto_batch._MIN_TPU_BATCH
    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(1)
    try:
        v = crypto_batch.create_batch_verifier()
        privs = [Ed25519PrivKey.generate() for _ in range(150)]
        for i, p in enumerate(privs):
            m = b"late|%d" % i
            v.add(p.pub_key(), m, p.sign(m))
        pending = v.verify_async()
        deadline = time.time() + 2.0
        while cal.device_samples == 0 and time.time() < deadline:
            time.sleep(0.005)
        assert cal.device_samples == 1
        flat_after_ready = cal.flat_s
        time.sleep(0.2)  # caller overlaps host work before resolving
        pending.result()
        assert cal.device_samples == 1, "result() must not re-observe"
        assert cal.flat_s == flat_after_ready, (
            "overlapped resolution leaked into the EWMA"
        )
    finally:
        crypto_batch.set_min_tpu_batch(old_min)
        crypto_batch.set_default_backend(old)
