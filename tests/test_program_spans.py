"""benchmark/program_spans.py: the per-layer readers over the program's
own spans, on hand-worked inputs; and the yardstick's own selfcheck
with the new entries in BENCHMARK.json.
"""

import time

import pytest

from benchmark import lookup, program_spans, selfcheck, trace_reduce
from cometbft_tpu.trace import Tracer, global_tracer

MS = 1e6  # ns


def test_idle_gaps_by_program_span_hand_worked():
    # a 100 ms slice; the device runs 10-40 and 60-90 ms, so it idles
    # 0-10, 40-60 and 90-100 ms: 40 ms. Three program spans on two
    # threads; the watcher's wait lies under the dispatcher's pack
    # from 42 to 45 ms, and a span that works takes a piece before one
    # that waits.
    events = {
        "devices": {
            "/device:TPU:0": {
                trace_reduce.MODULES: [
                    ["jit__verify_core(1)", 10 * MS, 30 * MS],
                    ["jit__verify_core(1)", 60 * MS, 30 * MS],
                ],
                trace_reduce.OPS: [
                    ["while.19", 10 * MS, 30 * MS],
                    ["while.19", 60 * MS, 30 * MS],
                ],
            }
        },
        "host": [[trace_reduce.SLICE, 0.0, 100 * MS]],
    }
    rows = [
        ("crypto.sched.device_wait", 5 * MS, 40 * MS),  # watcher thread
        ("ops.ed25519.pack", 42 * MS, 13 * MS),  # dispatcher thread
        ("validation.coalesce.fold", 92 * MS, 4 * MS),  # caller's thread
        ("ops.ed25519.pack", 150 * MS, 10 * MS),  # after the slice
    ]
    gaps = program_spans.gaps_by_program_span(events, rows)
    want = {
        # 0-5, 55-60, 90-92, 96-100
        program_spans.NO_SPAN: 0.016,
        "crypto.sched.device_wait": 0.007,  # 5-10, 40-42
        "ops.ed25519.pack": 0.013,  # 42-55
        "validation.coalesce.fold": 0.004,  # 92-96
    }
    assert set(gaps) == set(want)
    for k, v in want.items():
        assert gaps[k] == pytest.approx(v, abs=1e-12), k
    share, dark, idle = program_spans.unattributed(gaps)
    assert idle == pytest.approx(0.040)
    assert dark == pytest.approx(0.016)
    assert share == pytest.approx(40.0)
    # a trace with no slice or no device plane gives nothing
    assert program_spans.gaps_by_program_span({"devices": {}, "host": events["host"]}, rows) is None
    assert program_spans.gaps_by_program_span({"devices": events["devices"], "host": []}, rows) is None


def _x(name, start_ms, dur_ms, **args):
    return {
        "ph": "X", "name": name, "ts_ns": int(start_ms * MS),
        "dur_ns": int(dur_ms * MS), "args": args,
    }


def test_ring_window_keeps_what_starts_inside():
    stages = program_spans.STAGES
    # ticket 1, whole, inside the window (times in ms)
    whole = [
        (stages[0], 1000, 10), (stages[1], 1010, 20), (stages[2], 1030, 2),
        (stages[3], 1032, 18), (stages[4], 1050, 2), (stages[5], 1053, 47),
        (stages[6], 1100, 4),
    ]
    events = [_x(n, s, d, ticket=1) for n, s, d in whole]
    events += [
        _x(stages[0], 900, 10, ticket=0),  # before the window
        _x(stages[3], 2500, 10, ticket=3),  # after it
        _x(stages[0], 1900, 10, ticket=2),  # inside, but its ticket is cut
        _x(stages[3], 1500, 30),  # a direct dispatch: no ticket
        {"ph": "i", "name": "crypto.batch.dispatch", "ts_ns": int(1200 * MS),
         "dur_ns": 0, "args": {}},
    ]
    view = program_spans.ring_window(events, int(1000 * MS), int(2000 * MS))
    assert sorted(view["tickets"]) == [1, 2]
    assert view["by_name"][stages[0]] == [0.010, 0.010]
    assert view["by_name"][stages[3]] == [0.018, 0.030]
    assert "crypto.batch.dispatch" not in view["by_name"]
    # ticket 1 lives 1000 -> 1104 ms; its stages cover 103 ms of it
    share, dark_s, n = program_spans.unaccounted(view["tickets"])
    assert n == 1
    assert dark_s == pytest.approx(0.001)
    assert share == pytest.approx(100.0 / 104.0)
    assert program_spans.unaccounted({2: view["tickets"][2]}) is None


@pytest.fixture
def ring():
    tr = global_tracer()
    was = tr.enabled
    tr.enabled = True
    tr.clear()
    yield tr
    tr.enabled = was
    tr.clear()


def test_readers_over_the_process_tracer(ring):
    assert program_spans.CLOCKS_AGREE  # perf_counter is monotonic_ns here
    now = time.perf_counter()
    ns = lambda t: int(t * 1e9)  # noqa: E731
    record = {
        "window_s": 1.0,
        "seam_calls": [{"t": now}, {"t": now + 0.5}],
        "dispatches": [{"t": now + 0.9}],
        "blocks_applied": 50,
        "spans": [
            {"name": "blocksync.window.prepare", "dur_s": 0.010, "jobs": None},
            {"name": "blocksync.window.prepare", "dur_s": 0.015, "jobs": None},
            {"name": "blocksync.window.fetch_wait", "dur_s": 0.2, "jobs": None},
            {"name": "blocksync.window.apply", "dur_s": 0.3, "jobs": 3},
        ],
    }
    ring.complete("ops.ed25519.pack", ns(now - 0.2), 50_000_000, ticket=1)
    ring.complete("ops.ed25519.pack", ns(now + 0.1), 100_000_000, ticket=2)
    ring.complete("ops.ed25519.pack", ns(now + 0.6), 120_000_000, ticket=3)
    ring.complete("ops.ed25519.pack", ns(now + 1.5), 500_000_000, ticket=4)
    assert program_spans.pack_ms_per_dispatch(record) == pytest.approx(110.0)
    assert program_spans.enqueue_ms_per_dispatch(record) is None
    assert program_spans.ticket_unaccounted_share(record) is None
    assert program_spans.window_prepare_ms_per_block(record) == pytest.approx(0.5)
    assert program_spans.fetch_wait_share(record) == pytest.approx(20.0)
    # no traced slice: nothing to put the idle time down to
    assert program_spans.idle_unattributed_share(record) is None
    # a record with no stamp, a tracer switched off, a ring that has
    # dropped an event: nothing is read, nothing raises
    assert program_spans.pack_ms_per_dispatch({"window_s": 1.0}) is None
    assert program_spans.fetch_wait_share({"window_s": 1.0}) is None
    ring.enabled = False
    assert program_spans.pack_ms_per_dispatch(dict(record, _program_spans={})) is None
    ring.enabled = True
    for _ in range(ring.stats()["ring"]):
        ring.instant("filler")
    assert ring.stats()["dropped"] > 0
    assert program_spans.pack_ms_per_dispatch(dict(record, _program_spans={})) is None


def test_annotated_spans_reach_the_xplane(tmp_path):
    """A profiler session holds a tracer's live spans under their own
    names, whole, also where one is held open while others begin and
    end inside it out of stack order (blocksync's two waits across an
    await); complete() spans stay out."""
    jax = pytest.importorskip("jax")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    t = Tracer("xplane", size=64)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        outer = t.annotated_span("blocksync.window.fetch_wait", tid="blocksync")
        with t.annotated_span("validation.coalesce.build", ticket=5):
            time.sleep(0.004)
        cross = t.annotated_span("crypto.sched.route", ticket=5)
        time.sleep(0.002)
        outer.end()  # ends while `cross` is open: out of stack order
        time.sleep(0.002)
        cross.end()
        t.complete("crypto.sched.queue_wait", time.monotonic_ns() - 1000, 1000)
        t.annotated_span("mempool.not_the_programs_prefix").end()
    finally:
        jax.profiler.stop_trace()
    rows = program_spans.load_program_rows(trace_reduce.find_xplane(str(tmp_path)))
    by_name = {n: (s, d) for n, s, d in rows}
    assert sorted(by_name) == [
        "blocksync.window.fetch_wait", "crypto.sched.route",
        "validation.coalesce.build",
    ]
    ring = {e["name"]: e for e in t.snapshot()}
    for name, (start, dur) in by_name.items():
        # the annotation encloses the ring's span of the same name
        assert dur >= ring[name]["dur_ns"], name
        assert dur < ring[name]["dur_ns"] + 2 * MS, name
    fetch, route = by_name["blocksync.window.fetch_wait"], by_name["crypto.sched.route"]
    assert fetch[0] < route[0] < fetch[0] + fetch[1] < route[0] + route[1]


NEW = (
    "seam_build_ms_per_batch", "seam_fold_ms_per_batch", "sched_queue_wait_ms",
    "route_ms_per_ticket", "resolve_ms_per_ticket", "pack_ms_per_dispatch",
    "enqueue_ms_per_dispatch", "device_wait_ms_per_dispatch",
    "ticket_unaccounted_share", "idle_unattributed_share",
)


def test_spec_names_the_22_program_span_metrics():
    spec = lookup.load_spec()
    by_name = {m["name"]: m for m in spec["per_layer"]}
    want = [f"{n}.{s}" for n in NEW for s in ("verify", "catchup")]
    want += ["fetch_wait_share.catchup", "window_prepare_ms_per_block.catchup"]
    assert len(want) == 22
    layers = {m["layer"] for m in spec["per_layer"] if m["name"] not in want}
    for name in want:
        m = by_name[name]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["layer"] in layers  # a layer the benchmark already names
        assert m["moves"] == ("verify_rate" if name.endswith(".verify") else "catchup_rate")
        assert callable(lookup.load_reader(name))
        # a record with nothing in it: no reader raises
        assert lookup.load_reader(name)({}) is None
    # every cell that reports the end-to-end metric gets the readers
    # (qa175.catchup's one window a join outlasts the reactor's
    # once-a-second caught-up check, so its loop never waits for a block)
    for cell, n in (("val150.catchup", 12), ("qa175.catchup", 11), ("qa175.verify-only", 10)):
        got = [m["name"] for m in lookup.metrics_for(spec, cell, "per_layer")]
        assert len([g for g in got if g in want]) == n, cell


def test_selfcheck_passes(capsys):
    assert selfcheck.main() == 0
    assert "selfcheck: all passed" in capsys.readouterr().out
