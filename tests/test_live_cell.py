"""The deployment ``val150-kvstore-live`` and its cell
``val150.commit-live`` (BENCHMARK.json), as far as the CPU can show
them: ``verify_commit`` at live priority through the forced device
against the benchmark's plain ``VerifyCommit``, the one program the
configuration's ``PAD_MIN`` pin meets, the spans and the two ``pack``
args this cell reads, the open-loop generator's schedule and clock,
the five readers on hand-worked records, and the harness end to end at
8 validators. Times and rates come from the chip alone (PERF.md).
"""

import json
import os
import time

import numpy as np
import pytest

from benchmark import faults, live, lookup, opcount, program_spans, reference
from benchmark import reference_commit, run
from benchmark.generators import commit_live
from benchmark.tests import tiny
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import scheduler as sched_mod
from cometbft_tpu.crypto.lanes import LaneBatch
from cometbft_tpu.ops import ed25519 as ops_ed
from cometbft_tpu.trace import global_tracer
from cometbft_tpu.utils import device

CELL = "val150.commit-live"
NEW_METRICS = (
    "commit_build_ms.verify",
    "commit_fold_ms.verify",
    "precomp_kernel_roofline.verify",
    "key_expand_miss_share.verify",
    "send_lag_ms.verify",
)
SEED = 2_147_483_783  # past 32 signed bits, as the driver's can be
CONFIG8 = {"validators": 8, "voting_power": 10, "chain_id": "live-cell-8"}
NIL, ABSENT = reference_commit.FLAG_NIL, reference_commit.FLAG_ABSENT


def tiny_mix() -> dict:
    with open(os.path.join(lookup.HERE, "traffic", "tiny-commit-live.json")) as f:
        return json.load(f)


@pytest.fixture
def one_device(monkeypatch):
    """One local device, as the cell's chip: the unsharded program."""
    monkeypatch.setattr(device, "backend", lambda: device.Backend("cpu", "cpu", 1))


@pytest.fixture
def fresh_scheduler():
    old_backend = crypto_batch.default_backend()
    old_floor = crypto_batch._MIN_TPU_BATCH
    sched_mod.set_scheduler(sched_mod.VerifyScheduler())
    yield sched_mod.scheduler()
    sched_mod.set_scheduler(None)
    crypto_batch.set_default_backend(old_backend)
    crypto_batch.set_min_tpu_batch(old_floor)


@pytest.fixture
def ring():
    tr = global_tracer()
    was = tr.enabled
    tr.enabled = True
    tr.clear()
    yield tr
    tr.enabled = was
    tr.clear()


@pytest.fixture(scope="module")
def traffic():
    return commit_live.Traffic(CONFIG8, tiny_mix(), SEED)


def test_the_pool_is_a_function_of_the_seed(traffic):
    again = commit_live.Traffic(CONFIG8, tiny_mix(), SEED)
    other = commit_live.Traffic(CONFIG8, tiny_mix(), SEED + 1)
    plain = lambda t: [(p, v) for _, p, v in t.pool]  # noqa: E731
    assert plain(again) == plain(traffic) and again.expected_bad == traffic.expected_bad
    assert plain(other) != plain(traffic)
    assert len(traffic.expected_bad) == 8 and len(traffic.pool) == 64
    # the harness's first dispatch is a sound commit's lanes, whole
    assert len(traffic.warm_items()) == CONFIG8["validators"]
    with pytest.raises(IndexError):
        traffic.signed_height(64)


# --- (a) verify_commit, live priority, forced device, against VerifyCommit ----


def test_verify_commit_on_the_forced_device_gives_the_references_verdicts(
    traffic, fresh_scheduler, one_device
):
    """64 pool heights, one in eight corrupted (every kind, one past
    the lanes light verification reads), and five more: a nil vote, a
    nil vote with a bad signature, an absent signature, three absent
    ones (the tally fails), a bad signature behind an absent one. The
    real kernel (compact form on the CPU), one dispatch a commit."""
    light = 2 * CONFIG8["validators"] // 3 + 1
    kinds = {kind for kind, _ in traffic.expected_bad.values()}
    assert kinds == set(tiny_mix()["corrupt_kinds"])
    assert any(lane >= light for _, lane in traffic.expected_bad.values())
    cases = list(traffic.pool) + [
        traffic.signed_height(10, flags={2: NIL}),
        traffic.signed_height(11, "sig_s_byte", 2, flags={2: NIL}),
        traffic.signed_height(12, flags={5: ABSENT}),
        traffic.signed_height(13, flags={1: ABSENT, 4: ABSENT, 6: ABSENT}),
        traffic.signed_height(14, "sig_r_byte", 7, flags={0: ABSENT}),
    ]
    verifier = reference.Verifier()
    want = [
        reference_commit.full_verify(verifier, CONFIG8["chain_id"], vals, plain)
        for _, plain, vals in cases
    ]
    assert [w[:2] for w in want[64:]] == [
        (None, None), ("invalid_signature", 2), (None, None),
        ("not_enough_power", None), ("invalid_signature", 7),
    ]
    assert [w[2] for w in want[64:]] == [8, 8, 7, 5, 7]
    assert sum(w[0] is not None for w in want[:64]) == 8
    # light verification would pass what full verification refuses
    past = next(
        p for p, (_, lane) in traffic.expected_bad.items()
        if lane >= light and traffic.expected_bad[p][0] != "bad_key"
    )
    _, plain, vals = traffic.pool[past]
    assert reference.light_verify(verifier, CONFIG8["chain_id"], vals, plain)[0] is None
    assert want[past][0] == "invalid_signature"

    crypto_batch.set_default_backend("tpu")
    crypto_batch.set_min_tpu_batch(1)  # FORCED: the calibration is not asked
    before = fresh_scheduler.stats()
    got = [traffic.verify(job) for job, _, _ in cases]
    assert got == [w[:2] for w in want]
    after = fresh_scheduler.stats()
    assert after["device_dispatches"] - before["device_dispatches"] == len(cases)
    assert after["host_chunks"] == before["host_chunks"]
    assert after["by_class"]["live"] - before["by_class"]["live"] == sum(
        w[2] for w in want
    )
    last = ops_ed.LAST_DISPATCH
    assert (last["mode"], last["sharded"], last["cap"]) == ("precomp", False, 175)


# --- (b) the shape: one program under the configuration's pin -----------------


def _commit_items(n):
    rng = np.random.default_rng(35)
    keys = [rng.bytes(32) for _ in range(150)]
    msg, sig = rng.bytes(112), rng.bytes(64)
    return [(msg, keys[i % 150], sig) for i in range(n)]


def test_a_150_signature_ticket_meets_one_precomp_program(monkeypatch, one_device):
    """Under the configuration's pin (the bucket a 150-signature ticket
    gets unpinned) every ticket of the cell, the harness's first
    dispatch and a commit with absent votes among them, meets (256,
    precomp, 175); the one-chip cells' pin would send it to the plain
    16,384-lane program. No program is built here."""
    config = lookup.load_cell(lookup.load_spec(), CELL)["config_data"]
    assert config["pins"] == {"cometbft_tpu.ops.ed25519.PAD_MIN": 256}
    monkeypatch.delenv("GRAFT_PRECOMP_TUPLE", raising=False)
    shapes = {}
    for pad_min in (256, 128, 16_384):
        monkeypatch.setattr(ops_ed, "PAD_MIN", pad_min)
        for n in (150, 129, 101):
            ops_ed._pack(LaneBatch.from_items(_commit_items(n)))
            d = ops_ed.LAST_DISPATCH
            shapes[pad_min, n] = (d["lanes"], d["mode"], d["cap"])
            assert d["sharded"] is False and d["backend_key"][0] == "xla"
    assert {shapes[256, n] for n in (150, 129, 101)} == {(256, "precomp", 175)}
    assert shapes[128, 150] == (256, "precomp", 175)  # the bucket unpinned
    assert shapes[128, 101] == (128, "precomp", 175)  # a second program
    assert shapes[16_384, 150] == (16_384, "plain", 175)
    assert 100.0 * 150 / 256 == pytest.approx(58.6, abs=0.01)


# --- (c) the program's part: two spans, two args ---------------------------------


def test_verify_commit_leaves_its_two_stage_spans_under_the_tickets_id(
    traffic, ring, fresh_scheduler
):
    crypto_batch.set_default_backend("cpu")
    bad = next(iter(traffic.expected_bad))
    good = next(p for p in range(64) if p not in traffic.expected_bad)
    for p in (good, bad):
        ring.clear()
        verdict = traffic.verify(traffic.pool[p][0])
        assert (verdict[0] is None) == (p == good)
        spans = {e["name"]: e for e in ring.snapshot() if e["ph"] == "X"}
        build, fold = spans[live.BUILD], spans[live.FOLD]
        root = spans["crypto.sched.dispatch"]
        ticket = root["args"]["ticket"]
        assert isinstance(ticket, int)
        assert build["args"] == fold["args"] == {"ticket": ticket, "lanes": 8}
        assert build["tid"] == fold["tid"] == "validation"
        assert spans["crypto.sched.queue_wait"]["args"]["cls"] == "live"
        # build ends when submit() has returned, fold begins with the
        # verdicts in hand: the ticket's root lies between their starts
        assert build["ts_ns"] <= root["ts_ns"] <= build["ts_ns"] + build["dur_ns"]
        assert fold["ts_ns"] >= root["ts_ns"] + root["dur_ns"]


def test_verify_commit_under_a_stand_in_batch_route_records_no_fold(
    traffic, ring, fresh_scheduler
):
    """The benchmark's control replaces ``_run_batch_async``: no
    ticket, every verdict valid, and the errors a stand-in cannot
    hide (the tally) still raised."""
    undo = faults.accept_unverified(None)
    try:
        bad = next(iter(traffic.expected_bad))
        assert traffic.verify(traffic.pool[bad][0]) == (None, None)
        job, _, _ = traffic.signed_height(13, flags={1: ABSENT, 4: ABSENT, 6: ABSENT})
        assert traffic.verify(job) == ("not_enough_power", None)
    finally:
        undo()
    names = [e["name"] for e in ring.snapshot()]
    assert names.count(live.BUILD) == 2 and live.FOLD not in names


def _stub_program(*arrays):
    return np.ones(np.asarray(arrays[1]).shape[0], bool)


def test_pack_says_keys_and_expanded_on_a_cold_and_a_warm_key_cache(
    monkeypatch, ring, one_device
):
    monkeypatch.setattr(ops_ed, "verify_core_precomp_jit", _stub_program)
    monkeypatch.setattr(ops_ed, "verify_core_jit", _stub_program)
    monkeypatch.setattr(ops_ed, "_A_CACHE", {})
    signers = [reference.Signer(bytes([k + 1]) * 32) for k in range(6)]
    msg = b"m" * 100
    items = [(msg, s.public, s.sign(msg)) for s in signers] * 3  # 18 lanes, 6 keys
    more = items + [(msg, reference.undecodable_key(), items[0][2])]

    def pack_args(batch):
        ring.clear()
        ops_ed.verify_batch_async(batch).result()
        return next(e["args"] for e in ring.snapshot() if e["name"] == live.PACK)

    cold, warm, grown = pack_args(items), pack_args(items), pack_args(more)
    assert (cold["keys"], cold["expanded"]) == (6, 6)
    assert (warm["keys"], warm["expanded"]) == (6, 0)
    # a key that does not decompress is expanded (to nothing) once too
    assert (grown["keys"], grown["expanded"], grown["bad"]) == (7, 1, 1)
    assert pack_args(more)["expanded"] == 0
    assert ops_ed.LAST_DISPATCH["keys"] == 7
    # the plain form expands nothing on the host and says nothing
    monkeypatch.setattr(ops_ed, "PRECOMP_MAX_LANES", 0)
    plain = pack_args(items)
    assert plain["mode"] == "plain" and "keys" not in plain and "expanded" not in plain
    assert "keys" not in ops_ed.LAST_DISPATCH


# --- (d) the open loop -------------------------------------------------------------


def test_arrivals_are_a_function_of_seed_window_and_rate():
    a = commit_live.arrivals(SEED, 1, 200.0, 40.0)
    assert a == commit_live.arrivals(SEED, 1, 200.0, 40.0)
    assert a != commit_live.arrivals(SEED + 1, 1, 200.0, 40.0)
    assert a != commit_live.arrivals(SEED, 2, 200.0, 40.0)
    assert a == sorted(a) and 0.0 < a[0] and a[-1] < 40.0
    # a Poisson process GIVEN its count: the number offered is the
    # rate's whatever the seed, the gaps are exponential
    for seed in (SEED, SEED + 1, 7):
        assert len(commit_live.arrivals(seed, 1, 200.0, 40.0)) == 8000
        assert len(commit_live.arrivals(seed, 3, 25.0, 40.0)) == 1000
    gaps = np.diff(a)
    assert np.mean(gaps) == pytest.approx(1 / 200.0, rel=0.01)
    assert np.std(gaps) == pytest.approx(1 / 200.0, rel=0.1)
    assert np.mean(gaps > 3 / 200.0) == pytest.approx(np.exp(-3), rel=0.25)
    # a slice shorter than one gap still offers one request
    assert len(commit_live.arrivals(SEED, 2, 25.0, 0.01)) == 1


def test_latency_counts_from_the_due_instant_behind_a_busy_caller():
    """A fake clock, one caller, calls of 5 s: the second request,
    due at 1 s, is sent at 5 and done at 10, so it took 9 s, not 5;
    the fourth finds the window closed and is never sent."""
    now = [100.0]
    slept = []

    def sleep(dt):
        slept.append(dt)
        now[0] += dt

    def call(i):
        now[0] += 5.0
        return i * i

    t0, rows = commit_live.run_open_loop(
        [0.0, 1.0, 12.0, 13.0], call, 1, 17.0, clock=lambda: now[0], sleep=sleep
    )
    assert t0 == 100.0
    assert [(r["due"] - t0, r["sent"] - t0, r["done"] - t0) for r in rows[:3]] == [
        (0.0, 0.0, 5.0), (1.0, 5.0, 10.0), (12.0, 12.0, 17.0),
    ]
    assert [r["done"] - r["due"] for r in rows[:3]] == [5.0, 9.0, 5.0]
    assert [r["out"] for r in rows[:3]] == [0, 1, 4] and rows[3] is None
    assert slept == [2.0]  # only the third request was waited for


def test_open_loop_raises_what_a_call_raised_and_shares_the_schedule():
    seen = []

    def call(i):
        seen.append(i)
        if i == 5:
            raise KeyError("five")
        return i

    with pytest.raises(KeyError):
        commit_live.run_open_loop([0.0] * 40, call, 4, 10.0)
    _, rows = commit_live.run_open_loop(
        [0.001 * k for k in range(40)], lambda i: i, 4, 10.0
    )
    assert [r["out"] for r in rows] == list(range(40))  # each made once
    assert all(r["due"] <= r["sent"] <= r["done"] for r in rows)


# --- (e) the five readers on hand-worked records ------------------------------------


def _record(**kw) -> dict:
    return dict({"window_s": 40.0, "sched": {}, "dispatches": [], "seam_calls": []}, **kw)


def test_build_and_fold_readers_take_the_spans_means():
    rec = _record()
    rec[program_spans._KEY] = {
        "window": {
            "by_name": {live.BUILD: [0.001, 0.003], live.FOLD: [0.0005]},
            "tickets": {},
        }
    }
    assert live.commit_build_ms(rec) == pytest.approx(2.0)
    assert live.commit_fold_ms(rec) == pytest.approx(0.5)
    # a commit stream's window holds the coalesced seam's pair only
    other = _record()
    other[program_spans._KEY] = {
        "window": {"by_name": {program_spans.BUILD: [0.01]}, "tickets": {}}
    }
    assert live.commit_build_ms(other) is None and live.commit_fold_ms(other) is None
    assert live.commit_build_ms(_record()) is None  # no stamps, no window


def test_precomp_counts_are_the_plain_counts_less_one_square_root_chain():
    for cap, n in ((175, 1), (175, 150), (47, 7)):
        assert live.precomp_int32_ops(cap, n) == opcount.int32_ops(cap, n) - n * (
            20 * opcount.FE_MUL + 252 * opcount.FE_SQ
        )
        assert live.precomp_hbm_bytes(cap, n) == opcount.hbm_bytes(cap, n) + 320 * n
    assert 0 < live.precomp_int32_ops(175, 1) < opcount.int32_ops(175, 1)


def test_precomp_roofline_reader_on_a_hand_worked_record():
    rows = [{"sigs": 150, "cap": 175, "mode": "precomp", "lanes": 256}] * 2
    rec = _record(
        dispatches=rows, device_kind="TPU v5 lite",
        trace={"kernel_runs_s": [0.002, 0.004], "busy_s": 0.006, "window_s": 0.1},
    )
    ops_s = 150 * live.precomp_int32_ops(175, 1) / 6.16e12
    bytes_s = 150 * live.precomp_hbm_bytes(175, 1) / 819e9
    assert ops_s > bytes_s  # compute-bound, as the plain form
    assert live.precomp_kernel_roofline(rec) == pytest.approx(100.0 * ops_s / 0.003)
    assert live.precomp_kernel_roofline(rec) < 100.0
    plain = [dict(rows[0], mode="plain")]
    assert live.precomp_kernel_roofline(dict(rec, dispatches=plain)) is None
    assert live.precomp_kernel_roofline(dict(rec, trace=None)) is None
    assert live.precomp_kernel_roofline(dict(rec, dispatches=[])) is None


def test_key_expand_reader_sums_the_windows_pack_spans(ring):
    now = time.perf_counter()
    rec = _record(dispatches=[{"t": now - 1.0}, {"t": now + 1.0}])
    assert live.key_expand_miss_share(rec) is None  # no pack span yet
    t = time.monotonic_ns()
    ring.complete(live.PACK, t, 1000, tid="x", ticket=1, keys=150, expanded=150)
    ring.complete(live.PACK, t, 1000, tid="x", ticket=2, keys=150, expanded=0)
    ring.complete(live.PACK, t, 1000, tid="x", ticket=3, keys=151, expanded=1)
    ring.complete(live.PACK, t, 1000, tid="x", ticket=4, sigs=9)  # the plain form
    ring.complete(live.PACK, t - 5_000_000_000, 1000, tid="x", keys=99, expanded=99)
    assert live.key_expand_miss_share(rec) == pytest.approx(100.0 * 151 / 451)
    # a program whose pack says neither (the parent's) gives nothing
    ring.clear()
    ring.complete(live.PACK, t, 1000, tid="x", ticket=4, sigs=9)
    assert live.key_expand_miss_share(rec) is None
    assert live.key_expand_miss_share(_record()) is None


def test_send_lag_reader_takes_the_99th_percentile_of_sent_minus_due():
    requests = [
        {"due": 10.0 + k, "sent": 10.0 + k + 0.0001 * k, "done": 11.0 + k}
        for k in range(101)
    ]
    rec = _record(requests=requests)
    assert live.send_lag_ms(rec) == pytest.approx(1e3 * 0.0099)
    assert live.send_lag_ms(_record()) is None
    assert live.send_lag_ms(_record(requests=[])) is None


# --- (f) the cell in BENCHMARK.json and the harness end to end ------------------------


def test_lookup_finds_the_cell_its_metrics_and_leaves_the_others_theirs():
    spec = lookup.load_spec()
    cell = lookup.load_cell(spec, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "val150-kvstore-live", "commit-live",
    )
    assert len(cell["why"]) <= 200
    for word in ("commits/s", "32 callers", "2,048", "58.6%"):
        assert word in cell["why"], word
    config, mix = cell["config_data"], cell["mix"]
    base = lookup.load_cell(spec, "val150.catchup")["config_data"]
    for key in ("validators", "voting_power", "key_type", "backend"):
        assert config[key] == base[key], key
    assert config["priority"] == "live" and "full" in config["commit_verification"]
    assert sorted(config["reduced"]) == sorted(
        lookup.by_name(spec["configs"], cell["config"], "configuration")["reduced"]
    )
    assert callable(lookup.load_generator(mix["generator"]).Traffic)
    assert mix["rate_commits_per_s"] % 5 == 0 and mix["callers"] == 32
    assert mix["pool_heights"] * config["validators"] == 307_200
    assert mix["faults"] == ["accept_unverified", "verdict_altered"]
    # the ring holds a window of the cell's tickets with a quarter to spare
    tickets = mix["rate_commits_per_s"] * spec["run_seconds"] + mix["warm_requests"] + 12
    slots = global_tracer().stats()["ring"]
    assert slots >= 1.25 * 11 * tickets and slots & (slots - 1) == 0
    e2e = [m["name"] for m in lookup.metrics_for(spec, CELL, "end_to_end")]
    # not verify_batch_p95: six runs spread it by far more than 5% (PERF.md section 6)
    assert e2e == ["verify_rate", "setup_s"]
    per_layer = [m["name"] for m in lookup.metrics_for(spec, CELL, "per_layer")]
    assert per_layer[-5:] == list(NEW_METRICS)
    for name in NEW_METRICS:
        assert callable(lookup.load_reader(name))
    # the unlisted .verify metrics (less the seam's wrap), seven stage
    # readers of spans this cell leaves, none of the coalesced seam's
    verify = [n for n in per_layer if n.endswith(".verify")]
    assert len(verify) == 6 + 7 + 5
    for absent in (
        "seam_ms_per_batch.verify", "seam_build_ms_per_batch.verify",
        "seam_fold_ms_per_batch.verify", "ticket_unaccounted_share.verify",
        "kernel_roofline.verify",
    ):
        assert absent not in per_layer, absent
    assert not [n for n in per_layer if n.startswith("mesh_") or n.endswith(".catchup")]
    for other in ("val150.catchup", "qa175.verify-only", "qa175.catchup",
                  "val150.verify-only.mesh4"):
        names = [m["name"] for m in lookup.metrics_for(spec, other, "per_layer")]
        assert not [n for n in names if n in NEW_METRICS], other
    for seam_cell in ("qa175.verify-only", "val150.verify-only.mesh4"):
        assert "seam_ms_per_batch.verify" in [
            m["name"] for m in lookup.metrics_for(spec, seam_cell, "per_layer")
        ]


def tiny_spec() -> dict:
    """benchmark/tests/tiny.py's spec with a third cell: the tiny
    configuration under ``tiny-commit-live``, listed wherever
    BENCHMARK.json lists the real cell."""
    spec = tiny.spec()
    spec["workloads"].append(
        {"name": "tiny.commit-live", "config": "tiny-kvstore",
         "traffic": "tiny-commit-live", "chips": 1, "why": "tests"}
    )
    real = lookup.load_spec()
    for m, r in zip(
        spec["end_to_end"] + spec["per_layer"], real["end_to_end"] + real["per_layer"]
    ):
        if CELL in r.get("workloads", ()):
            # tiny.py maps the real cell to tiny.verify-only, a commit stream
            m["workloads"] = [
                w for w, rw in zip(m["workloads"], r["workloads"]) if rw != CELL
            ] + ["tiny.commit-live"]
    return spec


def drive(fault=None, seconds: float = 2.0) -> dict:
    undo = []

    def hook(traffic):
        if fault is not None:
            undo.append(faults.ALL[fault](traffic))

    try:
        return run.execute(
            tiny_spec(), "tiny.commit-live", SEED, seconds, False, tiny.DEVICE, fault=hook
        )
    finally:
        for u in undo:
            u()


@pytest.fixture
def harness(one_device, fresh_scheduler, monkeypatch):
    pad_min = ops_ed.PAD_MIN
    yield
    ops_ed.PAD_MIN = pad_min  # the tiny configuration pins it
    crypto_batch.calibration.__init__()


def test_tiny_commit_live_through_the_harness_reads_correct(harness):
    r = drive()
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 20
    assert set(r["metrics"]) == {"verify_rate", "setup_s"}
    assert 0 < r["metrics"]["verify_rate"]["value"] <= 8 * r["attempted"] / 2.0
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["compared"]) == {
        "verdicts_compared_min1", "commit_verdicts_differ", "reference_vs_plan_differ",
        "degraded_dispatches", "compilations_in_window", "commits_failed",
    }
    assert all(v == {"value": 0.0, "limit": 0.0} for v in r["compared"].values())


@pytest.mark.parametrize("fault", ["accept_unverified", "verdict_altered"])
def test_control_and_fault_are_not_correct_under_commit_live(fault, harness):
    r = drive(fault)
    assert r["correct"] is False, r["compared"]
    assert r["compared"]["commit_verdicts_differ"]["value"] > 0
    assert r["compared"]["reference_vs_plan_differ"]["value"] == 0
