"""The deployment ``val150-kvstore-mesh4`` and its cell
``val150.verify-only.mesh4`` (BENCHMARK.json), as far as the CPU's
virtual devices can show them: the ``mesh`` backend through the commit
seam against the benchmark's plain reference, the one-program shape set
the configuration's ``PAD_MIN`` pin buys, and the cell's four readers
on hand-worked inputs. Times and rates come from the chip alone
(PERF.md).
"""

import numpy as np
import pytest

import jax

from benchmark import lookup, mesh, opcount, record, reference, trace_reduce
from benchmark.generators import commit_stream
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import scheduler as sched_mod
from cometbft_tpu.crypto.lanes import LaneBatch
from cometbft_tpu.ops import ed25519 as ops_ed
from cometbft_tpu.trace import global_tracer
from cometbft_tpu.utils import device

CELL = "val150.verify-only.mesh4"
MESH_METRICS = (
    "mesh_kernel_roofline.verify",
    "mesh_put_ms_per_dispatch.verify",
    "mesh_fetch_ms_per_dispatch.verify",
    "mesh_shard_skew_ms.verify",
)
SEED = 2_147_483_777  # past 32 signed bits, as the driver's can be
MS = 1e6  # ns


# --- (a) the seam on the mesh backend against the plain reference ---------


@pytest.fixture
def mesh_backend():
    old_backend = crypto_batch.default_backend()
    sched_mod.set_scheduler(sched_mod.VerifyScheduler())
    crypto_batch.set_default_backend("mesh")
    yield sched_mod.scheduler()
    sched_mod.set_scheduler(None)
    crypto_batch.set_default_backend(old_backend)


@pytest.fixture(scope="module")
def pool():
    """64 signed heights of an 8-validator set from the seed, one in
    eight corrupted: every kind the commit stream knows, at least once."""
    spec = lookup.load_spec()
    mix = dict(
        lookup.load_cell(spec, CELL)["mix"],
        pool_heights=64, batch_commits=64, corrupt_one_in=8,
    )
    config = {
        "validators": 8, "voting_power": 10, "chain_id": "mesh-cell-8",
    }
    traffic = commit_stream.Traffic(config, mix, SEED)
    verifier = reference.Verifier()
    want = [
        reference.light_verify(verifier, config["chain_id"], vals, plain)[:2]
        for _, plain, vals in traffic.pool
    ]
    kinds = {kind for kind, _ in traffic.expected_bad.values()}
    assert kinds == set(commit_stream.KINDS)
    assert sum(w[0] is not None for w in want) == 8
    return traffic, want


@pytest.mark.parametrize("devices", [8, 1])
def test_seam_on_the_mesh_backend_gives_the_references_verdicts(
    devices, pool, mesh_backend, monkeypatch
):
    """Verdict and validator index of every commit equal
    ``VerifyCommitLight``'s; with a mesh the one dispatch is sharded
    over every device and nothing degrades, with one device visible
    the ``mesh-degraded`` count rises and the host gives the same
    answers."""
    traffic, want = pool
    if devices == 1:
        monkeypatch.setattr(
            device, "backend", lambda: device.Backend("cpu", "cpu", 1)
        )
    else:
        assert devices == len(jax.devices()) == device.backend().count
    ops_ed.LAST_DISPATCH.clear()
    before = mesh_backend.stats()
    handle = traffic._submit([job for job, _, _ in traffic.pool])
    got = [commit_stream._plain_error(e) for e in handle.result()]
    assert got == want
    after = mesh_backend.stats()
    degraded = after["degraded"] - before["degraded"]
    dispatches = after["device_dispatches"] - before["device_dispatches"]
    if devices == 1:
        assert (degraded, dispatches) == (1, 0)
        assert not ops_ed.LAST_DISPATCH
    else:
        assert (degraded, dispatches) == (0, 1)
        last = ops_ed.LAST_DISPATCH
        assert last["sharded"] is True and last["n_devices"] == devices
        assert last["lanes"] % devices == 0
        assert last["lanes"] >= 64 * traffic.light_lanes


# --- (b) the shape set: one program under the configuration's pin ---------


def _commit_items(n):
    """``n`` lanes shaped like a commit's: a sign-bytes-sized message
    (cap 175), a key of a 150-validator set, a signature."""
    rng = np.random.default_rng(29)
    keys = [rng.bytes(32) for _ in range(150)]
    msg = rng.bytes(112)
    sig = rng.bytes(64)
    return [(msg, keys[i % 150], sig) for i in range(n)]


@pytest.mark.parametrize(
    "pad_min, one_program", [(65_536, True), (16_384, False)]
)
def test_pad_min_pin_gives_the_mesh_one_program(
    pad_min, one_program, monkeypatch
):
    """Four devices. Under the configuration's pin the harness's first
    dispatch (150 signatures) and a 512-commit batch (51,712) meet the
    same (lanes, mode, cap): ``plain`` at 16,384 lanes a device. Under
    the one-chip configurations' pin the small one pads to 4,096 lanes
    a device and picks ``precomp``: a second whole-program compile.
    No program is built here."""
    config = lookup.load_cell(lookup.load_spec(), CELL)["config_data"]
    assert config["pins"] == {"cometbft_tpu.ops.ed25519.PAD_MIN": 65_536}
    monkeypatch.setattr(ops_ed, "PAD_MIN", pad_min)
    monkeypatch.delenv("GRAFT_PRECOMP_TUPLE", raising=False)
    monkeypatch.setattr(
        device, "backend", lambda: device.Backend("cpu", "cpu", 4)
    )
    built = []
    monkeypatch.setattr(
        ops_ed, "_sharded_fn",
        lambda mode: built.append(mode) or (object(), object()),
    )
    shapes = []
    for n in (150, 512 * 101):
        ops_ed._pack(LaneBatch.from_items(_commit_items(n)))
        d = ops_ed.LAST_DISPATCH
        assert d["sharded"] is True and d["n_devices"] == 4
        shapes.append((d["lanes"], d["mode"], d["cap"]))
    assert shapes[1] == (65_536, "plain", 175)
    assert (shapes[0] == shapes[1]) is one_program
    if not one_program:
        assert shapes[0] == (16_384, "precomp", 175)
    # the program asked for is the one the shape needs, and no other
    assert built == [mode for _, mode, _ in shapes]


# --- (d) the cell's readers ----------------------------------------------------


def _record(sigs, kernel_runs_s, n_devices):
    return {
        "device_kind": "TPU v5 lite",
        "dispatches": [
            {"sigs": s, "lanes": 65_536, "cap": 175, "n_devices": n_devices}
            for s in sigs
        ],
        "trace": {"kernel_runs_s": kernel_runs_s, "window_s": 1.0, "busy_s": 0.2},
    }


@pytest.mark.parametrize("n_devices", [4, 2, 1])
def test_mesh_kernel_roofline_is_the_one_chip_share_over_the_devices(n_devices):
    # two dispatches of 51,712 signatures; each device's run of its
    # shard took ~50 ms (the runs of every plane are pooled)
    runs = [0.050, 0.0502, 0.0501, 0.0499] * 2
    rec = _record([51_712, 51_712], runs, n_devices)
    one_chip = record.kernel_roofline(rec)
    got = mesh.mesh_kernel_roofline(rec)
    assert got == pytest.approx(one_chip / n_devices)
    peak = record.peaks("TPU v5 lite")["int32_ops_per_s"]["value"]
    least_s = opcount.int32_ops(175, 1) * 51_712 / (n_devices * peak)
    assert got == pytest.approx(100.0 * least_s / 0.0500500)
    if n_devices == 4:
        assert 12.0 < got < 12.3  # where one chip's peak would say 48-49%


def _plane(ends, dur=50 * MS, extra=()):
    rows = [["jit__verify_core(3)", e - dur, dur] for e in ends]
    return {trace_reduce.MODULES: rows + list(extra), trace_reduce.OPS: []}


def test_mesh_shard_skew_of_planes_ending_at_known_times():
    # a 400 ms slice, three dispatches on four planes. Ends (ms):
    # dispatch 1: 100, 101, 103, 102   -> 3
    # dispatch 2: 200, 200.5, 200.25, 201.5 -> 1.5
    # dispatch 3: 300, 300, 300, 300   -> 0         mean 1.5 ms
    # plane 0 also ran another program, plane 1 a run cut by the
    # slice's start: neither counts
    t0 = 1_000 * MS
    ends = [
        [100, 200, 300], [101, 200.5, 300], [103, 200.25, 300], [102, 201.5, 300],
    ]
    events = {
        "host": [(trace_reduce.SLICE, t0, 400 * MS)],
        "devices": {
            f"/device:TPU:{k}": _plane([t0 + e * MS for e in ends[k]])
            for k in range(4)
        },
    }
    events["devices"]["/device:TPU:0"][trace_reduce.MODULES].append(
        ["jit_quorum(7)", t0 + 350 * MS, 10 * MS]
    )
    events["devices"]["/device:TPU:1"][trace_reduce.MODULES].insert(
        0, ["jit__verify_core(3)", t0 - 20 * MS, 50 * MS]
    )
    assert mesh.kernel_ends(events)[1] == [t0 + e * MS for e in ends[1]]
    assert mesh.shard_skew_ms(mesh.kernel_ends(events)) == pytest.approx(1.5)
    # one device plane, no slice, no whole run: nothing to read
    one = dict(events, devices={"/device:TPU:0": events["devices"]["/device:TPU:0"]})
    assert mesh.shard_skew_ms(mesh.kernel_ends(one)) is None
    assert mesh.shard_skew_ms(mesh.kernel_ends(dict(events, host=[]))) is None
    empty = {k: _plane([]) for k in events["devices"]}
    assert mesh.shard_skew_ms(mesh.kernel_ends(dict(events, devices=empty))) is None


@pytest.fixture
def ring():
    tr = global_tracer()
    was = tr.enabled
    tr.enabled = True
    tr.clear()
    yield tr
    tr.enabled = was
    tr.clear()


def test_mesh_put_and_fetch_read_the_programs_child_spans(ring):
    import time

    now = time.perf_counter()
    ns = lambda t: int(t * 1e9)  # noqa: E731
    rec = {"seam_calls": [{"t": now}], "dispatches": [{"t": now + 1.0}]}
    assert mesh.mesh_put_ms_per_dispatch(dict(rec)) is None  # the parent
    ring.complete("ops.ed25519.put", ns(now + 0.1), 4_000_000, ticket=1, devices=4)
    ring.complete("ops.ed25519.put", ns(now + 0.4), 8_000_000, ticket=2, devices=4)
    ring.complete("ops.ed25519.put", ns(now + 2.0), 90_000_000, ticket=3, devices=4)
    ring.complete("ops.ed25519.fetch", ns(now + 0.3), 1_500_000, ticket=1, devices=4)
    assert mesh.mesh_put_ms_per_dispatch(dict(rec)) == pytest.approx(6.0)
    assert mesh.mesh_fetch_ms_per_dispatch(dict(rec)) == pytest.approx(1.5)


@pytest.mark.parametrize("name", MESH_METRICS)
def test_mesh_reader_finds_nothing_in_an_empty_record(name):
    assert lookup.load_reader(name)({}) is None


# --- the cell in BENCHMARK.json --------------------------------------------------

# collected here too: benchmark/tests lies outside the tier-1 path
from benchmark.tests.test_mesh_cell import (  # noqa: E402, F401
    test_lookup_finds_the_cell_and_its_metrics,
)
