"""The deployment ``val150-kvstore-w512`` and its cell
``val150.verify-only.512`` (BENCHMARK.json), as far as the CPU can show
them: the Pallas ladder's arithmetic in the interpreter against plain
Python integers, the ladder the program says it runs at each side of
``pallas_ladder.min_lanes``, and the cell's three readers on hand-worked
inputs. Times and rates come from the chip alone (PERF.md).
"""

import gzip
import json
import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark import lookup, reference
from benchmark import ladder as ladder_reader
from benchmark import trace_reduce
from cometbft_tpu.crypto.lanes import LaneBatch
from cometbft_tpu.ops import ed25519 as ops_ed
from cometbft_tpu.ops import fe25519 as fe
from cometbft_tpu.ops import pallas_ladder
from cometbft_tpu.trace import global_tracer
from cometbft_tpu.utils import device

CELL = "val150.verify-only.512"
SEED = 2_147_483_791  # past 32 signed bits, as a run's --seed can be
MS = 1e6  # ns


# --- (a) the Pallas ladder against plain integers -----------------------------


def _digits(scalars):
    """(64, N) int32 4-bit windows, least significant first."""
    return np.array(
        [[(s >> (4 * j)) & 15 for s in scalars] for j in range(64)], np.int32
    )


def _affine(x, y, z):
    inv = pow(z, reference.P - 2, reference.P)
    return x * inv % reference.P, y * inv % reference.P


def test_pallas_ladder_in_the_interpreter_matches_plain_integers():
    """128 lanes, one block: [S]B + [k]A for seeded keys and scalars,
    edge scalars among them (0, 1, 15, L - 1, every digit 15), against
    benchmark/reference.py's Python-integer point arithmetic, which
    imports nothing of the program."""
    n = 128
    rng = np.random.default_rng([SEED, 46])
    keys = []
    while len(keys) < n:
        point = reference.decompress(rng.bytes(32))
        if point is not None:
            keys.append(point)
    edge = [0, 1, 15, reference.L - 1, (1 << 252) - 1]
    s = edge + [int.from_bytes(rng.bytes(32), "little") % reference.L for _ in range(n - 5)]
    k = [int.from_bytes(rng.bytes(32), "little") % reference.L for _ in range(n - 5)] + edge
    limbs = np.stack([
        np.stack([fe.raw_limbs(c % reference.P) for c in point])
        for point in keys
    ], axis=2)  # (4, 20, n)
    A = tuple(
        tuple(jnp.asarray(limbs[c, j]) for j in range(fe.NLIMBS)) for c in range(4)
    )
    X, Y, Z, _ = pallas_ladder.straus_pallas(
        jnp.asarray(_digits(s)), jnp.asarray(_digits(k)), A, (n,), interpret=True
    )
    got = [
        [fe.from_limbs(np.asarray(fe.stack(c))[:, lane]) for c in (X, Y, Z)]
        for lane in range(n)
    ]
    for lane in range(n):
        want = reference._add(
            reference._mul(s[lane], reference._BASE), reference._mul(k[lane], keys[lane])
        )
        assert _affine(*got[lane]) == _affine(*want[:3]), lane


# --- (b) which ladder runs, and the tag that says so -----------------------------


@pytest.fixture
def on_chip(monkeypatch):
    """The program's choices as on one chip: an accelerator backend of
    one device, the ladder knobs unset. Nothing is compiled or run."""
    for var in ("GRAFT_PALLAS", "GRAFT_PALLAS_MIN_LANES", "GRAFT_PALLAS_SUBLANES",
                "GRAFT_PRECOMP_TUPLE"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(device, "on_cpu", lambda: False)
    monkeypatch.setattr(device, "backend", lambda: device.Backend("tpu", "tpu", 1))


def test_one_accepted_cell_on_each_side_of_min_lanes(on_chip):
    assert pallas_ladder.min_lanes() == 65_536
    assert pallas_ladder.pallas_enabled(65_536) is True
    assert pallas_ladder.pallas_enabled(16_384) is False
    spec = lookup.load_spec()
    pins = {
        w: lookup.load_cell(spec, w)["config_data"]["pins"]["cometbft_tpu.ops.ed25519.PAD_MIN"]
        for w in (CELL, "qa175.verify-only")
    }
    assert pins == {CELL: 65_536, "qa175.verify-only": 16_384}
    assert pallas_ladder.ladder_for(pins[CELL]) == "pallas"
    assert pallas_ladder.ladder_for(pins["qa175.verify-only"]) == "xla"


@pytest.mark.parametrize(
    "env, lanes, want",
    [
        ({}, 65_536, "pallas"),
        ({}, 131_072, "pallas"),
        ({}, 16_384, "xla"),
        ({"GRAFT_PALLAS": "0"}, 65_536, "xla"),
        ({"GRAFT_PALLAS": "1"}, 128, "pallas"),
        ({"GRAFT_PALLAS": "1"}, 200, "xla"),  # not a multiple of 128
        # 513 sublane rows: no VMEM-safe blocking, so the XLA ladder runs
        ({"GRAFT_PALLAS": "1"}, 513 * 128, "xla"),
    ],
)
def test_ladder_for_says_pallas_only_where_the_kernel_runs(on_chip, monkeypatch, env, lanes, want):
    for var, value in env.items():
        monkeypatch.setenv(var, value)
    assert pallas_ladder.ladder_for(lanes) == want
    if want == "xla" and lanes % 128 == 0 and pallas_ladder.pallas_enabled(lanes):
        r = lanes // 128
        assert pallas_ladder.effective_block(pallas_ladder.block_sublanes(), r) is None


@pytest.fixture
def ring():
    tr = global_tracer()
    was = tr.enabled
    tr.enabled = True
    tr.clear()
    yield tr
    tr.enabled = was
    tr.clear()


def _commit_items(n):
    rng = np.random.default_rng(46)
    keys = [rng.bytes(32) for _ in range(150)]
    msg, sig = rng.bytes(112), rng.bytes(64)
    return [(msg, keys[i % 150], sig) for i in range(n)]


@pytest.mark.parametrize(
    "pad_min, batch, want",
    [(65_536, 512 * 101, "pallas"), (16_384, 128 * 117, "xla")],
)
def test_dispatch_spans_say_the_ladder(on_chip, ring, monkeypatch, pad_min, batch, want):
    """Under the pin of this cell and of ``qa175.verify-only``, a full
    batch of the cell (51,712 or 14,976 signatures) and the harness's
    first dispatch (150) go out tagged with the ladder ``_straus`` takes
    at that width: ``pack`` and ``enqueue`` say it, LAST_DISPATCH too.
    The program itself is not built here."""
    monkeypatch.setattr(ops_ed, "PAD_MIN", pad_min)
    ran = []
    monkeypatch.setattr(ops_ed, "verify_core_jit", lambda *a: ran.append(a[2].shape) or a[1])
    monkeypatch.setattr(ops_ed, "_put", lambda arrays, tuple_a, shardings: arrays)
    for n in (batch, 150):
        ops_ed.verify_batch_async(LaneBatch.from_items(_commit_items(n)))
        d = ops_ed.LAST_DISPATCH
        assert (d["lanes"], d["mode"], d["ladder"]) == (pad_min, "plain", want)
        assert d["backend_key"][0] == want and d["interpret"] is False
    assert ran == [(32, pad_min)] * 2
    spans = [e for e in ring.snapshot() if e["ph"] == "X"]
    for name in ("ops.ed25519.pack", "ops.ed25519.enqueue"):
        assert [e["args"]["ladder"] for e in spans if e["name"] == name] == [want, want]


# --- (c) the cell's readers ---------------------------------------------------------


def _trace_small():
    path = os.path.join(lookup.HERE, "testdata", "trace_small.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_ladder_reader_finds_nothing_in_an_xla_trace():
    events = _trace_small()
    assert trace_reduce.KERNEL in json.dumps(events)  # the verify program ran
    assert ladder_reader.mean_ladder_ms(ladder_reader.ladder_runs(events)) is None


def test_ladder_ms_of_a_trace_with_known_ladder_ops():
    """A 300 ms slice on one plane: three whole runs of the verify
    program (one more cut by the slice's end), each with a ladder op
    of 40, 41 and 42 ms among other ops; a ladder op outside any run
    is not counted. Mean 41 ms."""
    t0 = 1_000 * MS
    modules = [
        ["jit__verify_core(7)", t0 + (10 + 90 * k) * MS, 60 * MS] for k in range(4)
    ]
    ops = []
    for k in range(3):
        start = t0 + (10 + 90 * k) * MS
        ops += [
            ["while.3", start, 5 * MS],
            ["_ladder_call.1", start + 10 * MS, (40 + k) * MS],
        ]
    ops.append(["_ladder_call.1", t0 + 75 * MS, 2 * MS])  # between two runs
    events = {
        "host": [(trace_reduce.SLICE, t0, 300 * MS)],
        "devices": {"/device:TPU:0": {trace_reduce.MODULES: modules, trace_reduce.OPS: ops}},
    }
    runs = ladder_reader.ladder_runs(events)
    assert runs == pytest.approx([0.040, 0.041, 0.042])
    assert ladder_reader.mean_ladder_ms(runs) == pytest.approx(41.0)
    assert ladder_reader.ladder_runs(dict(events, host=[])) == []


def test_ladder_roofline_of_a_hand_worked_dispatch(monkeypatch):
    # 2,349,888 int32 operations a signature: 51,712 signatures are
    # 19.727 ms at the assumed 6.16e12 ops/s, against 5,872 bytes a
    # lane (0.371 ms at 819 GB/s): compute bounds it
    assert ladder_reader.ladder_int32_ops(1) == 2_349_888
    assert ladder_reader.ladder_hbm_bytes(1) == 5_872
    monkeypatch.setattr(ladder_reader, "ladder_ms_per_dispatch", lambda rec: 50.0)
    rec = {
        "device_kind": "TPU v5 lite",
        "dispatches": [{"sigs": 51_712, "lanes": 65_536}] * 2,
    }
    want = 100.0 * 2_349_888 * 51_712 / 6.16e12 / 0.050
    assert ladder_reader.ladder_roofline(rec) == pytest.approx(want)
    assert 39.4 < want < 39.5


def test_pallas_dispatch_share_reads_the_pack_spans_ladder(ring):
    now = time.perf_counter()
    ns = lambda t: int(t * 1e9)  # noqa: E731
    rec = {"seam_calls": [{"t": now}], "dispatches": [{"t": now + 1.0}]}
    # the parent's spans carry no ladder: nothing to read
    ring.complete("ops.ed25519.pack", ns(now + 0.1), 1_000_000, ticket=1, lanes=65_536)
    assert ladder_reader.pallas_dispatch_share(dict(rec)) is None
    ring.clear()
    for k, ladder in enumerate(("pallas", "pallas", "xla")):
        ring.complete("ops.ed25519.pack", ns(now + 0.1 * (k + 1)), 1_000_000, ticket=k, ladder=ladder)
    ring.complete("ops.ed25519.pack", ns(now + 2.0), 1_000_000, ticket=9, ladder="xla")  # after
    assert ladder_reader.pallas_dispatch_share(dict(rec)) == pytest.approx(200.0 / 3)


def test_the_reader_keys_on_the_kernels_name():
    assert pallas_ladder._ladder_call.__name__ == ladder_reader.LADDER_OP


@pytest.mark.parametrize(
    "name",
    ["ladder_ms_per_dispatch.verify", "ladder_roofline.verify", "pallas_dispatch_share.verify"],
)
def test_ladder_reader_finds_nothing_in_an_empty_record(name):
    assert lookup.load_reader(name)({}) is None


# --- the cell in BENCHMARK.json -------------------------------------------------------

# collected here too: benchmark/tests lies outside the tier-1 path
from benchmark.tests.test_w512_cell import (  # noqa: E402, F401
    test_lookup_finds_the_w512_cell_and_its_metrics,
)
