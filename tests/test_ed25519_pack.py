"""``ops/ed25519._pack`` against a plain per-item fill.

The reference below is the loop ``_pack`` ran before its fill became a
handful of array operations, kept here as the test's own code: one
Python step a lane, four ``np.frombuffer`` calls and five column
writes. ``_pack`` must hand ``_put`` the same arrays, value for
value, in shape, dtype, C-contiguity and ownership of the buffer, for
every input, bad lanes included: the verify program's HLO and its
argument shapes then stay what they were. No kernel runs here.

``_pack`` takes the lanes by columns (crypto/lanes.LaneBatch). Every
case is packed from both constructions of one: ``from_items``, the
boundary every caller with tuples crosses, and the columns themselves
as the verify seam holds them (built below a lane at a time); and in
one block of lanes as in several small ones (``PACK_BLOCK``).
"""

import functools

import numpy as np
import pytest

from cometbft_tpu.crypto import ref_ed25519
from cometbft_tpu.crypto.lanes import LaneBatch
from cometbft_tpu.ops import ed25519 as ops_ed
from cometbft_tpu.ops import fe25519 as fe
from cometbft_tpu.utils import device

PAD_MIN = 8
CAPS = (47, 175, 431, 943)
MODES = ("plain", "precomp", "precomp_tuple")
FORMS = ("tuples", "columns")
# lanes transposed at a time: the module's own (every case one block)
# and one that cuts the 37-lane cases into 16 + 16 + 5
BLOCKS = (ops_ed.PACK_BLOCK, 16)


def _loop_pack(items, mode, n_dev=1):
    """(arrays in argument order, bad, lanes, cap) by the per-item
    loop."""
    n = len(items)
    max_len = max(len(m) for m, _, _ in items)
    cap = next(c for c in CAPS if max_len <= c)
    np_ = PAD_MIN
    while np_ < n:
        np_ *= 2
    if np_ % n_dev:
        np_ += n_dev - (np_ % n_dev)
    use_precomp = mode != "plain"
    msgs = np.zeros((cap, np_), np.uint8)
    lens = np.zeros(np_, np.int32)
    pks = np.zeros((32, np_), np.uint8)
    rs = np.zeros((32, np_), np.uint8)
    ss = np.zeros((32, np_), np.uint8)
    a_arr = (
        np.zeros((4, fe.NLIMBS, np_), np.int32) if use_precomp else None
    )
    bad = np.zeros(np_, bool)
    for i, (m, pk, sig) in enumerate(items):
        if len(pk) != 32 or len(sig) != 64:
            bad[i] = True
            continue
        if use_precomp:
            pt = ref_ed25519.point_decompress(bytes(pk))
            if pt is None:  # pubkey fails ZIP-215 decompression
                bad[i] = True
                continue
            x, y, _z, t = pt
            a_arr[:, :, i] = np.stack(
                [fe.raw_limbs(v) for v in (x, y, 1, t)]
            )
        msgs[: len(m), i] = np.frombuffer(m, np.uint8)
        lens[i] = len(m)
        pks[:, i] = np.frombuffer(pk, np.uint8)
        rs[:, i] = np.frombuffer(sig[:32], np.uint8)
        ss[:, i] = np.frombuffer(sig[32:], np.uint8)
    arrays = (
        (msgs, lens, a_arr, pks, rs, ss)
        if use_precomp
        else (msgs, lens, pks, rs, ss)
    )
    return arrays, bad, np_, cap


@functools.lru_cache(maxsize=None)
def _keys():
    """(five keys that decompress, one 32-byte string that does not:
    a y with no x on the curve), from a fixed generator."""
    rng = np.random.default_rng(28)
    on, off = [], None
    while len(on) < 5 or off is None:
        k = rng.bytes(32)
        if ref_ed25519.point_decompress(k) is None:
            off = off or k
        elif len(on) < 5:
            on.append(k)
    return tuple(on), off


def _on_curve(i):
    return _keys()[0][i]


def _off_curve():
    return _keys()[1]


def _items(lengths, seed=1):
    """One lane a message length: random bytes, a key of the set
    (repeating, as a validator set does), a random signature."""
    rng = np.random.default_rng(seed)
    on_curve, _ = _keys()
    return [
        (rng.bytes(n), on_curve[i % len(on_curve)], rng.bytes(64))
        for i, n in enumerate(lengths)
    ]


def _columns(items):
    """The lanes by columns, built a lane at a time: a refused lane's
    rows zero and its position in ``bad``."""
    n = len(items)
    keys = np.zeros((n, 32), np.uint8)
    sigs = np.zeros((n, 64), np.uint8)
    bad = []
    for i, (_m, pk, sig) in enumerate(items):
        if len(pk) != 32 or len(sig) != 64:
            bad.append(i)
            continue
        keys[i] = np.frombuffer(pk, np.uint8)
        sigs[i] = np.frombuffer(sig, np.uint8)
    return LaneBatch(
        [bytes(m) for m, _, _ in items], keys, sigs, np.array(bad, np.intp)
    )


def _batch(form, items):
    return LaneBatch.from_items(items) if form == "tuples" else _columns(items)


def _ragged():
    return _items(np.random.default_rng(7).integers(0, 171, 37).tolist())


def _with(items, at, m=None, pk=None, sig=None):
    m0, pk0, sig0 = items[at]
    items[at] = (
        m0 if m is None else m, pk0 if pk is None else pk,
        sig0 if sig is None else sig,
    )
    return items


def _as(kind, items):
    return [tuple(kind(f) for f in it) for it in items]


def _mixed_buffers(items):
    kinds = (bytes, bytearray, memoryview)
    return [
        tuple(kinds[(i + j) % 3](f) for j, f in enumerate(it))
        for i, it in enumerate(items)
    ]


CASES = {
    "ragged": _ragged,
    "equal_lengths": lambda: _items([111] * 21),
    "one_item": lambda: _items([60]),
    "exactly_a_bucket": lambda: _items([33, 90] * PAD_MIN),
    "a_bucket_and_one": lambda: _items([5] * (2 * PAD_MIN + 1)),
    "key_31_bytes": lambda: _with(_ragged(), 5, pk=_on_curve(0)[:31]),
    "key_33_bytes_and_empty_key": lambda: _with(
        _with(_ragged(), 0, pk=_on_curve(1) + b"\x00"), 36, pk=b""
    ),
    "signature_63_bytes": lambda: _with(_ragged(), 36, sig=b"\x07" * 63),
    "signature_65_bytes": lambda: _with(_ragged(), 1, sig=b"\x07" * 65),
    "empty_message": lambda: _with(_ragged(), 9, m=b""),
    "only_empty_messages": lambda: _items([0] * 3),
    # the refused lane's message still sets cap: 175 -> 431
    "bad_lane_is_the_longest": lambda: _with(
        _ragged(), 11, m=b"\xaa" * 300, sig=b"\x01" * 10
    ),
    "every_lane_bad": lambda: [
        (m, pk[:16], sig) for m, pk, sig in _items([40, 0, 100])
    ],
    "first_and_last_bad": lambda: _with(
        _with(_ragged(), 0, pk=b"k"), 36, sig=b"s"
    ),
    "bytearrays": lambda: _as(bytearray, _ragged()),
    "memoryviews": lambda: _as(memoryview, _ragged()),
    "mixed_buffers_with_a_bad_lane": lambda: _mixed_buffers(
        _with(_ragged(), 20, pk=bytearray(30))
    ),
    # on the device's side of the line in plain, refused in precomp
    "undecompressible_key": lambda: _with(
        _with(_ragged(), 3, pk=_off_curve()), 30, pk=_off_curve()
    ),
    "undecompressible_and_short_keys": lambda: _with(
        _with(_with(_ragged(), 2, pk=_off_curve()), 3, pk=b"short"),
        4, pk=bytearray(_off_curve()),
    ),
    "longest_cap": lambda: _items([943, 1, 432]),
    # in blocks of 16: refused lanes either side of both block edges
    # and at the end of the ragged last block, of each kind
    "bad_lanes_on_block_edges": lambda: _with(
        _with(
            _with(
                _with(_with(_ragged(), 15, pk=b"k" * 31), 16, sig=b"s" * 65),
                31, pk=_off_curve(),
            ),
            32, sig=b"",
        ),
        36, pk=_off_curve(),
    ),
    "three_whole_blocks": lambda: _with(
        _items([3, 170, 90] * 16), 47, pk=b""
    ),
    "four_blocks_and_a_bucket_of_padding": lambda: _with(
        _items(np.random.default_rng(9).integers(0, 48, 65).tolist()),
        64, sig=b"\x01" * 63,
    ),
}


@pytest.fixture
def shapes(monkeypatch):
    """Small buckets, and the device count and kernel form the case
    asks for (``_pack`` reads all three from its module)."""

    def _set(mode, n_dev=1, block=ops_ed.PACK_BLOCK):
        monkeypatch.setattr(ops_ed, "PAD_MIN", PAD_MIN)
        monkeypatch.setattr(ops_ed, "PACK_BLOCK", block)
        monkeypatch.setattr(
            ops_ed, "PRECOMP_MAX_LANES", 0 if mode == "plain" else 4096
        )
        if mode == "precomp_tuple":
            monkeypatch.setenv("GRAFT_PRECOMP_TUPLE", "1")
        else:
            monkeypatch.delenv("GRAFT_PRECOMP_TUPLE", raising=False)
        program = (None, None) if n_dev == 1 else (object(), object())
        monkeypatch.setattr(
            ops_ed.device, "backend",
            lambda: device.Backend("cpu", "cpu", n_dev),
        )
        monkeypatch.setattr(ops_ed, "_sharded_fn", lambda mode: program)
        return program

    return _set


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert type(g) is np.ndarray
        assert g.shape == w.shape and g.dtype == w.dtype
        assert g.flags.c_contiguous and g.flags.owndata
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", CASES)
def test_pack_hands_over_the_loops_arrays(case, mode, form, block, shapes):
    shapes(mode, block=block)
    items = CASES[case]()
    want, want_bad, lanes, cap = _loop_pack(items, mode)
    fn, arrays, tuple_a, shardings, bad = ops_ed._pack(_batch(form, items))
    assert shardings is None
    _same(arrays, want)
    _same([bad], [want_bad])
    for a, b in zip(arrays, want):  # a refused lane is zero everywhere
        assert not a[..., want_bad].any() and not b[..., want_bad].any()
    assert tuple_a == (mode == "precomp_tuple")
    assert fn is {
        "plain": ops_ed.verify_core_jit,
        "precomp": ops_ed.verify_core_precomp_jit,
        "precomp_tuple": ops_ed.verify_core_precomp_tuple_jit,
    }[mode]
    last = ops_ed.LAST_DISPATCH
    assert (last["lanes"], last["cap"], last["mode"], last["precomp"]) == (
        lanes, cap, mode, mode != "plain"
    )
    assert type(last["lanes"]) is int and type(last["cap"]) is int
    assert last["sharded"] is False and last["n_devices"] == 1


@pytest.mark.parametrize("block", (ops_ed.PACK_BLOCK, 2))
@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("mode", MODES)
def test_pack_rounds_the_lanes_up_to_the_devices(mode, form, block, shapes):
    """Three devices: 8 lanes become 9, the program is the sharded
    one and the arrays go by its shardings."""
    program, program_shardings = shapes(mode, n_dev=3, block=block)
    items = _with(_items([10, 20, 30, 40, 50]), 2, pk=_off_curve())
    want, want_bad, lanes, cap = _loop_pack(items, mode, n_dev=3)
    assert lanes == 9
    fn, arrays, _tuple_a, shardings, bad = ops_ed._pack(_batch(form, items))
    _same(arrays, want)
    _same([bad], [want_bad])
    assert fn is program and shardings is program_shardings
    last = ops_ed.LAST_DISPATCH
    assert (last["sharded"], last["n_devices"], last["lanes"]) == (
        True, 3, 9
    )


def test_pack_expands_a_distinct_key_once(shapes, monkeypatch):
    shapes("precomp")
    calls = []
    expand = ops_ed._expand_pubkey
    monkeypatch.setattr(
        ops_ed, "_expand_pubkey",
        lambda pk: calls.append(pk) or expand(pk),
    )
    items = _with(_with(_ragged(), 3, pk=_off_curve()), 30, pk=_off_curve())
    ops_ed._pack(LaneBatch.from_items(items))
    assert sorted(calls) == sorted(_keys()[0] + (_off_curve(),))
    assert all(type(pk) is bytes for pk in calls)


@pytest.mark.parametrize("case", CASES)
def test_from_items_gives_the_columns(case):
    """The boundary's one pass over the tuples: the columns a lane at
    a time gives, the messages as ``bytes`` (the callers' own
    objects where they are)."""
    items = CASES[case]()
    got, want = LaneBatch.from_items(items), _columns(items)
    assert len(got) == len(items)
    for a, (b, _, _) in zip(got.msgs, items):
        assert type(a) is bytes and a == b
        assert a is b or type(b) is not bytes
    for g, w in ((got.keys, want.keys), (got.sigs, want.sigs)):
        assert g.shape == w.shape and g.dtype == np.uint8
        assert g.flags.c_contiguous
        np.testing.assert_array_equal(g, w)
    assert got.bad.tolist() == want.bad.tolist()


def test_verify_batch_async_takes_both_forms(shapes, monkeypatch):
    """The boundary: tuples become columns inside the ``pack`` span,
    columns pass; ``len`` of what was handed in counts the verdicts."""
    shapes("plain")
    packed = []
    real = ops_ed._pack
    monkeypatch.setattr(
        ops_ed, "_pack", lambda batch: packed.append(batch) or real(batch)
    )
    monkeypatch.setattr(ops_ed, "_put", lambda arrays, tuple_a, sh: arrays)
    monkeypatch.setattr(
        ops_ed, "verify_core_jit",
        lambda msgs, lens, *rest: np.ones(lens.shape, bool),
    )
    items = _with(_ragged(), 4, pk=b"short")
    batch = LaneBatch.from_items(items)
    for handed in (items, batch):
        oks = ops_ed.verify_batch_async(handed).result()
        assert oks.tolist() == [i != 4 for i in range(len(items))]
    assert type(packed[0]) is LaneBatch and packed[1] is batch
