"""One ticket, one timeline (docs/TRACE.md): a verify ticket leaves one
span a stage on the process tracer, every one carrying its id, in
order, inside the ticket's root span; and ops/ed25519's two stage spans
say what LAST_DISPATCH says, with the placement (``ops.ed25519.put``)
and the verdict read (``ops.ed25519.fetch``) as child spans of one name
on one device and on a mesh.

The kernel PROGRAM is stubbed (the jitted callables, one level beneath
``verify_batch_async``), so ``_pack`` and ``_put`` run for real on
the CPU backend at the smallest bucket without the minutes of kernel
compile; the kernel's math has its own lane (test_ed25519_verify.py).
"""

import copy
import dataclasses

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import scheduler as sched_mod
from cometbft_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
from cometbft_tpu.node.inprocess import make_genesis
from cometbft_tpu.ops import ed25519 as ops_ed
from cometbft_tpu.trace import current_ticket, global_tracer, ticket_scope
from cometbft_tpu.types.validation import verify_commits_coalesced_async
from cometbft_tpu.utils import device
from cometbft_tpu.utils.chaingen import make_chain

BUILD = "validation.coalesce.build"
QUEUE_WAIT = "crypto.sched.queue_wait"
ROUTE = "crypto.sched.route"
PACK = "ops.ed25519.pack"
ENQUEUE = "ops.ed25519.enqueue"
PUT = "ops.ed25519.put"
FETCH = "ops.ed25519.fetch"
DEVICE_WAIT = "crypto.sched.device_wait"
RESOLVE = "crypto.sched.resolve"
FOLD = "validation.coalesce.fold"
ROOT = "crypto.sched.dispatch"

STAGES = {
    "host": [BUILD, QUEUE_WAIT, ROUTE, RESOLVE, FOLD],
    "device": [
        BUILD, QUEUE_WAIT, ROUTE, PACK, ENQUEUE, DEVICE_WAIT, RESOLVE, FOLD,
    ],
}
# child span -> the stage it lies inside (device-routed tickets only)
CHILDREN = {PUT: ENQUEUE, FETCH: RESOLVE}


def _stub_program(*arrays):
    """What a jitted verify program returns, by host math: the lanes
    of the padded arrays verified one by one."""
    msgs, lens, pks, rs, ss = (np.asarray(a) for a in (
        arrays[0], arrays[1], arrays[-3], arrays[-2], arrays[-1]
    ))
    out = np.zeros(lens.shape[0], bool)
    for i in range(lens.shape[0]):
        if not pks[:, i].any():
            continue  # padding
        out[i] = Ed25519PubKey(pks[:, i].tobytes()).verify(
            msgs[: lens[i], i].tobytes(),
            rs[:, i].tobytes() + ss[:, i].tobytes(),
        )
    return out


def _devices(monkeypatch, n):
    """``n`` local devices, as ops/ed25519 and the mesh route see
    them (conftest gives JAX 8 virtual ones to place arrays on)."""
    monkeypatch.setattr(
        device, "backend", lambda: device.Backend("cpu", "cpu", n)
    )


@pytest.fixture
def stubbed_kernel(monkeypatch):
    """The three kernel programs replaced; one device, so the plain
    (unsharded) path of verify_batch_async runs whole."""
    _devices(monkeypatch, 1)
    for name in (
        "verify_core_jit",
        "verify_core_precomp_jit",
        "verify_core_precomp_tuple_jit",
    ):
        monkeypatch.setattr(ops_ed, name, _stub_program)


@pytest.fixture
def stubbed_mesh(monkeypatch):
    """Four devices and the sharded program replaced: the arrays are
    placed by the real program's shardings, shard by shard."""
    from cometbft_tpu.parallel.mesh import make_mesh
    from cometbft_tpu.parallel.sharded_verify import core_shardings

    _devices(monkeypatch, 4)
    mesh = make_mesh(4)
    monkeypatch.setattr(
        ops_ed, "_sharded_fn",
        lambda mode: (_stub_program, core_shardings(mesh, mode)),
    )


@pytest.fixture
def ring():
    tr = global_tracer()
    was = tr.enabled
    tr.enabled = True
    tr.clear()
    yield tr
    tr.enabled = was
    tr.clear()


@pytest.fixture
def fresh_scheduler():
    old_backend = crypto_batch.default_backend()
    old_floor = crypto_batch._MIN_TPU_BATCH
    sched_mod.set_scheduler(sched_mod.VerifyScheduler())
    yield
    sched_mod.set_scheduler(None)
    crypto_batch.set_default_backend(old_backend)
    crypto_batch.set_min_tpu_batch(old_floor)


@pytest.fixture(scope="module")
def chain():
    gen, pvs = make_genesis(4, chain_id="ticket-timeline")
    src = make_chain(gen, [pv.priv_key for pv in pvs], 6)
    yield gen, src
    src.close_stores()


def _jobs(chain, heights, bad_height=None):
    gen, src = chain
    vs = gen.validator_set()
    store = src.block_store
    jobs = []
    for h in heights:
        commit = store.load_seen_commit(h)
        if h == bad_height:
            commit = copy.deepcopy(commit)
            sig = bytearray(commit.signatures[0].signature)
            sig[0] ^= 1
            commit.signatures[0] = dataclasses.replace(
                commit.signatures[0], signature=bytes(sig)
            )
        jobs.append((vs, store.load_block_meta(h).block_id, h, commit))
    return gen.chain_id, jobs


def _route(route):
    if route == "host":
        crypto_batch.set_default_backend("cpu")
    else:
        # forced past the floor: the mesh route where the kernel
        # fixture gave more than one device, the tpu route on one
        # (the decision and ops/ed25519 read the same device count)
        crypto_batch.set_min_tpu_batch(1)
        crypto_batch.set_default_backend(
            "mesh" if device.backend().count > 1 else "tpu"
        )


def _ticket_spans(ring, ticket):
    return [
        e for e in ring.snapshot()
        if e["ph"] == "X" and e["args"].get("ticket") == ticket
    ]


@pytest.mark.parametrize("route", ["host", "device"])
def test_ticket_leaves_every_stage_in_order(
    route, chain, ring, fresh_scheduler, stubbed_kernel, monkeypatch
):
    _route(route)
    chain_id, jobs = _jobs(chain, range(1, 5), bad_height=2)
    handle = verify_commits_coalesced_async(chain_id, jobs)
    errors = handle.result()
    assert [e is None for e in errors] == [True, False, True, True]
    ticket = handle._batch.ticket_id
    assert isinstance(ticket, int)

    spans = _ticket_spans(ring, ticket)
    by_name = {e["name"]: e for e in spans}
    want = STAGES[route]
    # one span a stage and the root, nothing per signature or commit
    children = list(CHILDREN) if route == "device" else []
    assert sorted(e["name"] for e in spans) == sorted(
        want + children + [ROOT]
    )
    end = lambda e: e["ts_ns"] + e["dur_ns"]  # noqa: E731
    chain_of = [by_name[n] for n in want]
    # the queue wait starts at the submit stamp, inside build (which
    # ends when submit() has returned); every later stage starts no
    # earlier than the one before it ended
    build, queue_wait = chain_of[0], chain_of[1]
    assert build["ts_ns"] <= queue_wait["ts_ns"] <= end(build)
    for before, after in zip(chain_of[1:], chain_of[2:]):
        assert after["ts_ns"] >= end(before), (before["name"], after["name"])
    # the root: stamped at submit, holding every stage between the
    # seam's two
    root = by_name[ROOT]
    inner = chain_of[1:-1]
    assert root["ts_ns"] == queue_wait["ts_ns"]
    assert end(root) >= end(by_name[RESOLVE])
    assert sum(e["dur_ns"] for e in inner) <= root["dur_ns"]
    assert by_name[FOLD]["ts_ns"] >= end(root)
    # the counts each span carries
    lanes = sum(3 for _ in jobs)  # light: 3 of 4 equal validators
    assert build["args"]["jobs"] == len(jobs)
    assert build["args"]["lanes"] == lanes
    assert by_name[FOLD]["args"]["jobs"] == len(jobs)
    assert queue_wait["args"]["cls"] == "catchup"
    assert queue_wait["args"]["depth"] == 0
    # the coalesced seam hands its lanes on by columns
    assert by_name[ROUTE]["args"] == {
        "ticket": ticket, "lanes": lanes, "path": route, "form": "columns",
    }
    assert root["args"]["lanes"] == lanes
    # rows: a thread's role
    assert build["tid"] == by_name[FOLD]["tid"] == "validation"
    assert by_name[ROUTE]["tid"] == "crypto.sched.dispatcher"
    if route == "device":
        assert by_name[PACK]["tid"] == "crypto.sched.dispatcher"
        assert by_name[PACK]["args"]["sigs"] == lanes
        assert by_name[PACK]["args"]["form"] == "columns"
        assert by_name[PACK]["args"]["blocks"] == 1
        assert by_name[DEVICE_WAIT]["tid"] == "crypto.sched.watcher"
        assert by_name[RESOLVE]["tid"] == "crypto.sched.watcher"
    else:
        assert by_name[RESOLVE]["tid"] == "crypto.sched.host"


@pytest.mark.parametrize("route", ["host", "device"])
def test_span_count_does_not_grow_with_the_ticket(
    route, chain, ring, fresh_scheduler, stubbed_kernel, monkeypatch
):
    _route(route)
    counts = []
    for heights in (range(1, 2), range(1, 6)):
        handle = verify_commits_coalesced_async(*_jobs(chain, heights))
        assert handle.result() == [None] * len(heights)
        counts.append(len(_ticket_spans(ring, handle._batch.ticket_id)))
    children = len(CHILDREN) if route == "device" else 0
    assert counts[0] == counts[1] == len(STAGES[route]) + children + 1


def test_second_ticket_sees_the_lanes_queued_ahead(
    chain, ring, fresh_scheduler, monkeypatch
):
    """``depth`` of the queue wait: lanes not yet resolved at submit."""
    crypto_batch.set_default_backend("cpu")
    sched = sched_mod.scheduler()
    with sched._cv:  # the dispatcher cannot pop while both are queued
        a = verify_commits_coalesced_async(*_jobs(chain, range(1, 3)))
        b = verify_commits_coalesced_async(*_jobs(chain, range(3, 4)))
    assert a.result() == [None, None] and b.result() == [None]
    waits = {
        e["args"]["ticket"]: e["args"]["depth"]
        for e in ring.snapshot() if e["name"] == QUEUE_WAIT
    }
    assert waits == {a._batch.ticket_id: 0, b._batch.ticket_id: 6}


@pytest.mark.parametrize("devices", [1, 4])
def test_put_and_fetch_are_children_with_one_name_on_both_paths(
    devices, chain, ring, fresh_scheduler, monkeypatch, request
):
    """``ops.ed25519.put`` inside ``enqueue`` and ``ops.ed25519.fetch``
    inside ``resolve``, under the ticket's id, on one device (the
    ``jnp.asarray`` puts) and on a mesh (the arrays placed by the
    sharded program's shardings, the verdicts read from every
    device)."""
    request.getfixturevalue("stubbed_kernel" if devices == 1 else "stubbed_mesh")
    _route("device")
    chain_id, jobs = _jobs(chain, range(1, 5), bad_height=3)
    handle = verify_commits_coalesced_async(chain_id, jobs)
    assert [e is None for e in handle.result()] == [True, True, False, True]
    last = dict(ops_ed.LAST_DISPATCH)
    assert last["sharded"] is (devices > 1)
    assert last["n_devices"] == devices and last["lanes"] % devices == 0
    ticket = handle._batch.ticket_id
    by_name = {e["name"]: e for e in _ticket_spans(ring, ticket)}
    end = lambda e: e["ts_ns"] + e["dur_ns"]  # noqa: E731
    for child, parent in CHILDREN.items():
        c, p = by_name[child], by_name[parent]
        assert p["ts_ns"] <= c["ts_ns"] and end(c) <= end(p), child
        assert c["tid"] == p["tid"]
        assert c["args"]["ticket"] == ticket
        assert c["args"]["devices"] == devices
    assert by_name[PUT]["args"]["bytes"] == by_name[ENQUEUE]["args"]["bytes"]
    assert by_name[FETCH]["args"]["lanes"] == last["lanes"]
    assert by_name[PACK]["args"]["devices"] == devices
    assert by_name[PACK]["args"]["lanes_per_device"] == last["lanes"] // devices


@pytest.mark.parametrize("refused", [0, 2])
def test_pack_and_enqueue_say_what_last_dispatch_says(
    ring, stubbed_kernel, refused
):
    sk = Ed25519PrivKey.generate()
    pk = sk.pub_key().key_bytes
    items = []
    for i in range(5):
        msg = b"pack-enqueue-%d" % i
        items.append((msg, pk, sk.sign(msg)))
    items[3] = (items[3][0] + b"!", pk, items[3][2])
    want = [True, True, True, False, True]
    if refused:
        # refused before the device: a 31-byte key, a 63-byte signature
        items[1] = (items[1][0], pk[:31], items[1][2])
        items[4] = (items[4][0], pk, items[4][2][:63])
        want[1] = want[4] = False
    assert current_ticket() == (None, None)
    got = ops_ed.verify_batch_async(items).wait().result()
    assert list(got) == want
    with ticket_scope(42, "some.row"):
        assert current_ticket() == (42, "some.row")
        ops_ed.verify_batch_async(items).result()
    assert current_ticket() == (None, None)

    last = ops_ed.LAST_DISPATCH
    assert last["lanes"] == ops_ed.PAD_MIN  # the smallest bucket
    ev = [e for e in ring.snapshot() if e["name"].startswith("ops.ed25519.")]
    # a span is recorded at its end: the child before its parent
    assert [e["name"] for e in ev] == [PACK, PUT, ENQUEUE, FETCH] * 2
    for pack, enqueue, ticket, tid, expanded in (
        (ev[0], ev[2], None, "ops.ed25519", 1),
        (ev[4], ev[6], 42, "some.row", 0),
    ):
        lanes = last["lanes"]
        want_args = {
            "ticket": ticket, "sigs": len(items), "lanes": lanes,
            "cap": last["cap"], "mode": last["mode"],
            "ladder": last["ladder"], "bad": refused,
            "devices": 1, "lanes_per_device": lanes,
            "form": "tuples", "blocks": 1,
        }
        if last["precomp"]:
            # one distinct 32-byte key, new to the expanded-key LRU
            # in the first dispatch and found there in the second
            want_args.update(keys=1, expanded=expanded)
        assert pack["args"] == want_args
        # msgs + lens + A (precomp) + pks, rs, ss
        want_bytes = last["cap"] * lanes + 4 * lanes + 3 * 32 * lanes
        if last["precomp"]:
            want_bytes += 4 * 20 * 4 * lanes
        assert enqueue["args"] == {
            "ticket": ticket, "lanes": lanes, "ladder": last["ladder"],
            "bytes": want_bytes,
        }
        assert pack["tid"] == enqueue["tid"] == tid
        assert enqueue["ts_ns"] >= pack["ts_ns"] + pack["dur_ns"]


def test_stand_in_batch_route_still_folds(chain, ring, monkeypatch):
    """The benchmark's control replaces ``_run_batch_async`` with an
    object that has only ``result()``: the seam must take it (no
    ticket, so no fold stage), not raise."""
    from cometbft_tpu.types import validation

    class AllValid:
        def __init__(self, n):
            self.n = n

        def result(self):
            return [True] * self.n

    monkeypatch.setattr(
        validation, "_run_batch_async",
        lambda items, cache, priority=None, label="": AllValid(len(items)),
    )
    chain_id, jobs = _jobs(chain, range(1, 4), bad_height=2)
    assert verify_commits_coalesced_async(chain_id, jobs).result() == [None] * 3
    seam = [e for e in ring.snapshot() if e["name"].startswith("validation.")]
    assert [e["name"] for e in seam] == [BUILD]
    assert seam[0]["args"]["ticket"] is None


@pytest.mark.parametrize("cached", [False, True], ids=["no-cache", "cache"])
def test_seam_spans_keep_their_names_rows_and_args(
    cached, chain, ring, fresh_scheduler
):
    """The seam plans and folds by columns since PR 31; what its two
    spans say did not move (22 readers sit on them): the names, the
    row, and exactly ``ticket``, ``jobs``, ``lanes``: there is no
    per-signature fallback to count. ``lanes`` is every lane of the
    batch, cache hits included."""
    from cometbft_tpu.types.signature_cache import SignatureCache

    crypto_batch.set_default_backend("cpu")
    cache = SignatureCache() if cached else None
    chain_id, jobs = _jobs(chain, range(1, 5), bad_height=2)
    tickets = []
    for window in (jobs[:3], jobs[1:]):  # the second hits the cache
        handle = verify_commits_coalesced_async(chain_id, window, cache=cache)
        errors = handle.result()
        assert [e is None for e in errors] == [
            j[2] != 2 for j in window
        ]
        tickets.append(handle._batch.ticket_id)
    seam = [e for e in ring.snapshot() if e["name"].startswith("validation.")]
    assert [e["name"] for e in seam] == [BUILD, FOLD] * 2
    for e, ticket in zip(seam, [tickets[0]] * 2 + [tickets[1]] * 2):
        assert e["tid"] == "validation" and e["ph"] == "X"
        assert e["args"] == {"ticket": ticket, "jobs": 3, "lanes": 9}
    if cached:
        # heights 2 (its two good lanes) and 3 were fed by the first
        assert (cache.hits, cache.misses) == (5, 9 + 4)


@pytest.mark.parametrize("form", ["tuples", "columns"])
def test_route_and_pack_say_the_form_and_pack_its_blocks(
    form, chain, ring, fresh_scheduler, stubbed_kernel, monkeypatch
):
    """``form`` on ``crypto.sched.route`` and ``ops.ed25519.pack``:
    how the ticket's lanes arrived (``verify_commit`` hands tuples, the
    coalesced seam columns); ``blocks``: in how many blocks of
    ``PACK_BLOCK`` lanes ``_pack`` transposed them."""
    from cometbft_tpu.types.validation import verify_commit

    _route("device")
    monkeypatch.setattr(ops_ed, "PACK_BLOCK", 4)
    chain_id, jobs = _jobs(chain, range(1, 4))
    if form == "columns":
        handle = verify_commits_coalesced_async(chain_id, jobs)
        assert handle.result() == [None] * 3
        lanes = 9  # light: 3 of 4 equal validators, three commits
    else:
        vals, block_id, height, commit = jobs[0]
        verify_commit(chain_id, vals, block_id, height, commit)
        lanes = 4
    by_name = {
        e["name"]: e for e in ring.snapshot()
        if e["name"] in (ROUTE, PACK)
    }
    assert by_name[ROUTE]["args"]["form"] == form
    assert by_name[PACK]["args"]["form"] == form
    assert by_name[PACK]["args"]["sigs"] == lanes
    assert by_name[PACK]["args"]["blocks"] == -(-lanes // 4)
    stats = sched_mod.scheduler().stats()
    assert stats["columnar_tickets"] == (form == "columns")
    assert stats["tickets"] == 1
