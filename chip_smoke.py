#!/usr/bin/env python3
"""chip_smoke.py - the main path on one TPU chip, as its quickest proof.

A fresh node catches up on a 150-validator chain by blocksync; the
commit signatures are verified on the TPU through the one
VerifyScheduler (crypto/scheduler.py -> ops/ed25519.py). One process,
no children. Phases:

  catchup   BlockSyncReactor + StorePeerClient into a fresh memdb node,
            backend "tpu" (routing left to the calibration), then the
            same catch-up on the serial "cpu" backend: same height,
            block hash and app hash; device dispatches > 0, none
            degraded, every dispatch on the XLA ladder of one device.
  verdicts  a 150-signature commit and a 19,200-lane batch straight to
            the kernel with a known set of corrupted lanes, compared
            lane for lane with the serial host verifier and, on the
            commit and on every corrupted lane, with the pure-Python
            crypto/ref_ed25519.py.
  pallas    (--pallas) one dispatch of every signature of the chain in
            the 65,536 bucket, where the Pallas ladder is the default.
  sharded   (--chips 4, alone) the 19,200-lane batch lane-sharded over
            four chips plus the psum quorum tally, against the serial
            host verifier.

The default run compiles ONE verify program: ops/ed25519.PAD_MIN is
pinned so every dispatch pads to one lane bucket and every message is
a vote's sign-bytes (one cap). It refuses to run without a TPU, and no
option relaxes that. The last line of stdout is one JSON object.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import importlib.metadata
import json
import os
import sys
import tempfile
import time

N_VALS = 150  # BASELINE.json config 3 / upstream's QA nets
N_BLOCKS = 385  # three full 128-block verify windows + the tip block
FULL_LENGTH = 10_000  # that config's chain length: the benchmark's business
WINDOW = 128
PAD_MIN = 32_768  # the one lane bucket of the default run
BATCH_COMMITS = 128  # 128 x 150 = 19,200 lanes


def say(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    """A phase did not show what it must; the run ends non-zero."""


def need(cond, *what) -> None:
    if not cond:
        raise SmokeFailure(*what)


# --- compilation accounting ---------------------------------------------


class CompileLog:
    """Every program the backend built or loaded from the cache, by
    name and seconds (jax.monitoring), and the persistent-cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        from jax import monitoring

        self.programs: list = []  # (fun_name, seconds)
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_secs)
        monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **kw) -> None:
        if event == self.EVENT:
            self.programs.append((kw.get("fun_name", "?"), secs))

    def _on_event(self, event, **kw) -> None:
        if event == self.HIT:
            self.cache_hits += 1

    def verify_programs(self) -> list:
        return [p for p in self.programs if "verify_core" in p[0]]

    @contextlib.contextmanager
    def none_expected(self, phase: str):
        """A phase runs warmed-up programs only."""
        before = len(self.programs)
        yield
        new = self.programs[before:]
        say(f"{phase}: compilations in phase = {len(new)}")
        if new:
            raise SmokeFailure(f"{phase} compiled {new}")


def cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


# --- the chain ----------------------------------------------------------


def build_chain(seed: int, n_vals: int, n_blocks: int, home: str):
    """(genesis, source NodeParts): a signed chain in a sqlite store
    under ``home``, from a seed and a seed-derived genesis time
    instead of the clock."""
    import numpy as np

    import cometbft_tpu.types as T
    from cometbft_tpu.config.config import test_config
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.node.inprocess import build_node
    from cometbft_tpu.types.genesis import GenesisDoc
    from cometbft_tpu.utils.chaingen import make_chain

    rng = np.random.default_rng(seed)
    privs = [Ed25519PrivKey.from_seed(rng.bytes(32)) for _ in range(n_vals)]
    gen = GenesisDoc(
        chain_id="smoke-chain",
        validators=[T.Validator(p.pub_key(), 10) for p in privs],
        genesis_time_ns=(1_700_000_000 + seed % 1_000_000) * 1_000_000_000,
    )
    cfg = test_config(home)
    cfg.base.db_backend = "sqlite"
    src = build_node(gen, None, config=cfg, home=home)
    make_chain(gen, privs, n_blocks, txs_per_block=1, node=src)
    return gen, src


def commit_lanes(src, chain_id: str, heights):
    """Kernel items (msg, pubkey32, sig64) for every signature of the
    commits at ``heights``, messages being the votes' own sign-bytes."""
    from cometbft_tpu.types.validation import _commit_sign_bytes

    vals = src.state.validators
    items = []
    for h in heights:
        commit = src.block_store.load_seen_commit(h)
        for i, cs in enumerate(commit.signatures):
            items.append(
                (
                    _commit_sign_bytes(chain_id, commit, cs),
                    vals.get_by_index(i).pub_key.key_bytes,
                    cs.signature,
                )
            )
    return items


def unpinned_bucket(n: int) -> int:
    """The lane bucket ``n`` signatures get without the PAD_MIN pin
    (ops/ed25519._pad_n at its shipped floor of 128)."""
    p = 128
    while p < n:
        p *= 2
    return p


# --- phase: catchup -----------------------------------------------------


@contextlib.contextmanager
def dispatch_log():
    """Record every device dispatch: (signatures, LAST_DISPATCH)."""
    from cometbft_tpu.ops import ed25519 as ed

    log: list = []
    real = ed.verify_batch_async

    def recording(items):
        handle = real(items)
        log.append((len(items), dict(ed.LAST_DISPATCH)))
        return handle

    ed.verify_batch_async = recording
    try:
        yield log
    finally:
        ed.verify_batch_async = real


def catchup_leg(gen, src, backend: str, window: int, timeout_s: float):
    """One blocksync catch-up of a fresh memdb node from ``src`` on
    verify backend ``backend``; returns what the legs are compared on."""
    from cometbft_tpu.blocksync import BlockSyncReactor
    from cometbft_tpu.config.config import test_config
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import scheduler as crypto_sched
    from cometbft_tpu.node.inprocess import build_node
    from cometbft_tpu.utils.chaingen import StorePeerClient

    crypto_batch.set_default_backend(backend)
    crypto_sched.set_scheduler(None)  # fresh counters per leg
    cfg = test_config(".")
    cfg.base.db_backend = "memdb"
    fresh = build_node(gen, None, config=cfg)
    limit = src.block_store.height()

    async def run():
        caught = asyncio.Event()
        reactor = BlockSyncReactor(
            fresh.state,
            fresh.block_exec,
            fresh.block_store,
            on_caught_up=lambda st: caught.set(),
            verify_window=window,
        )
        reactor.tracer = fresh.tracer
        reactor.pool.set_peer_range("src", StorePeerClient(src), 1, limit)
        t0 = time.perf_counter()
        await reactor.start()
        await asyncio.wait_for(caught.wait(), timeout_s)
        wall = time.perf_counter() - t0
        await reactor.stop()
        return wall, dict(reactor.pipeline_stats)

    with dispatch_log() as dispatches:
        wall, pipeline = asyncio.run(run())
    sched = crypto_sched.scheduler()
    need(sched.drain(timeout=60.0), "verify scheduler did not drain")
    h = fresh.block_store.height()
    return {
        "backend": backend,
        "height": h,
        "block_hash": fresh.block_store.load_block(h).hash(),
        "app_hash": fresh.state_store.load().app_hash,
        "wall_s": wall,
        "pipeline": pipeline,
        "sched": sched.stats(),
        "dispatches": dispatches,
        "windows": [
            e["args"].get("jobs")
            for e in fresh.tracer.snapshot()
            if e["name"] == "blocksync.window.verify_wait"
        ],
    }


def phase_catchup(
    gen, src, window: int, device_backend: str = "tpu",
    expect_device: bool = True, timeout_s: float = 600.0,
) -> None:
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.ops import ed25519 as ed

    n_blocks = src.block_store.height()
    dev = catchup_leg(gen, src, device_backend, window, timeout_s)
    ref = catchup_leg(gen, src, "cpu", window, timeout_s)
    cal = crypto_batch.calibration
    for leg in (dev, ref):
        sigs = leg["sched"]["lanes"]
        say(
            f"catchup[{leg['backend']}]: height={leg['height']} "
            f"signatures={sigs} "
            f"wall_s={leg['wall_s']!r} sched={leg['sched']} "
            f"pipeline={leg['pipeline']}"
        )
        say(
            f"catchup[{leg['backend']}]: window blocks (one commit each) = "
            f"{leg['windows']}"
        )
    say(
        "catchup[%s]: device dispatches (signatures -> pinned lanes | "
        "bucket without the pin, kernel form without the pin):"
        % dev["backend"]
    )
    for n, d in dev["dispatches"]:
        b = unpinned_bucket(n)
        say(
            f"  {n} -> {d['lanes']} | {b} "
            f"{'precomp' if b <= ed.PRECOMP_MAX_LANES else 'plain'}"
        )
    say(
        f"catchup: calibration flat_s={cal.flat_s!r} lane_s={cal.lane_s!r} "
        f"host_s={cal.host_s!r} crossover={cal.crossover()} "
        f"device_samples={cal.device_samples}"
    )
    say(
        f"catchup: final block hash {dev['block_hash'].hex()} app hash "
        f"{dev['app_hash'].hex()} (serial reference: "
        f"{ref['block_hash'].hex()} {ref['app_hash'].hex()})"
    )
    need(dev["height"] >= n_blocks - 2, (dev["height"], n_blocks))
    for key in ("height", "block_hash", "app_hash"):
        need(dev[key] == ref[key], (key, dev[key], ref[key]))
    need(
        dev["block_hash"]
        == src.block_store.load_block(dev["height"]).hash(),
        "caught-up block differs from the source chain's",
    )
    need(dev["sched"]["degraded"] == 0, dev["sched"])
    need(ref["sched"]["device_dispatches"] == 0, ref["sched"])
    if expect_device:
        need(dev["sched"]["device_dispatches"] > 0, dev["sched"])
        for n, d in dev["dispatches"]:
            check_dispatch(d, ladder="xla", n_devices=1)


def check_dispatch(d: dict, ladder: str, n_devices: int) -> None:
    need(d["backend_key"][0] == ladder, d)
    need(not d["interpret"], d)
    need(d["n_devices"] == n_devices, d)
    need(d["sharded"] == (n_devices > 1), d)


# --- phase: verdicts ----------------------------------------------------


def _undecodable_key() -> bytes:
    """A 32-byte string that is no curve point even under ZIP-215."""
    from cometbft_tpu.crypto import ref_ed25519 as ref

    for y in range(2, 1000):
        raw = y.to_bytes(32, "little")
        if ref.point_decompress(raw) is None:
            return raw
    raise SmokeFailure("no undecodable key found")


def corrupt(items, seed: int, per_kind: int):
    """Copy of ``items`` with ``per_kind`` lanes of each corruption;
    returns (items, {lane: kind})."""
    import numpy as np

    from cometbft_tpu.crypto import ref_ed25519 as ref

    rng = np.random.default_rng(seed + 1)
    lanes = rng.choice(len(items), size=5 * per_kind, replace=False)
    out = list(items)
    bad = {}
    kinds = ("sig_r_byte", "sig_s_byte", "wrong_key", "bad_key", "s_plus_L")
    for j, lane in enumerate(int(x) for x in lanes):
        kind = kinds[j % len(kinds)]
        msg, pk, sig = out[lane]
        if kind == "sig_r_byte":
            sig = sig[:5] + bytes([sig[5] ^ 0x40]) + sig[6:]
        elif kind == "sig_s_byte":
            sig = sig[:40] + bytes([sig[40] ^ 0x01]) + sig[41:]
        elif kind == "wrong_key":
            pk = items[(lane + 1) % len(items)][1]
            if pk == items[lane][1]:  # one-validator rehearsal
                pk = bytes([pk[0] ^ 1]) + pk[1:]
        elif kind == "bad_key":
            pk = _undecodable_key()
        else:  # non-canonical S: S + L still fits 32 bytes
            s = int.from_bytes(sig[32:], "little") + ref.L
            sig = sig[:32] + s.to_bytes(32, "little")
        out[lane] = (msg, pk, sig)
        bad[lane] = kind
    return out, bad


def host_verdicts(items):
    """The serial host verifier (the ``cpu`` backend)."""
    from cometbft_tpu.crypto.batch import CpuBatchVerifier
    from cometbft_tpu.crypto.keys import Ed25519PubKey

    v = CpuBatchVerifier()
    for msg, pk, sig in items:
        v.add(Ed25519PubKey(pk), msg, sig)
    return v.verify()[1]


def compare_verdicts(name, items, bad, got, ref_all: bool) -> list:
    """``got`` against the host verifier on every lane, against
    ref_ed25519 on the corrupted lanes (every lane when ``ref_all``),
    and against what the corruption must give. Returns the host
    verifier's verdicts."""
    from cometbft_tpu.crypto import ref_ed25519 as ref

    got = [bool(v) for v in got]
    host = [bool(v) for v in host_verdicts(items)]
    want = [i not in bad for i in range(len(items))]
    ref_lanes = range(len(items)) if ref_all else sorted(bad)
    ref_diff = [
        i
        for i in ref_lanes
        if ref.verify_zip215(items[i][1], items[i][0], items[i][2]) != got[i]
    ]
    host_diff = [i for i in range(len(items)) if host[i] != got[i]]
    want_diff = [i for i in range(len(items)) if want[i] != got[i]]
    say(
        f"verdicts[{name}]: lanes={len(items)} corrupted={len(bad)} "
        f"rejected={got.count(False)} differ_from_host={len(host_diff)} "
        f"differ_from_ref_ed25519={len(ref_diff)} "
        f"(ref checked on {len(ref_lanes)} lanes) "
        f"differ_from_expected={len(want_diff)}"
    )
    need(not host_diff, (name, "host", host_diff[:10]))
    need(not ref_diff, (name, "ref", ref_diff[:10]))
    need(not want_diff, (name, "expected", want_diff[:10]))
    return host


def phase_verdicts(src, chain_id: str, seed: int, n_commits: int) -> None:
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import scheduler as crypto_sched
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.ops import ed25519 as ed

    # one commit through the scheduler, the path the node takes, with
    # the device forced
    commit, bad = corrupt(commit_lanes(src, chain_id, [2]), seed, 1)
    crypto_batch.set_default_backend("tpu")
    floor = crypto_batch._MIN_TPU_BATCH
    crypto_batch.set_min_tpu_batch(1)
    try:
        ticket = crypto_sched.scheduler().submit(
            [(Ed25519PubKey(pk), msg, sig) for msg, pk, sig in commit],
            label="chip-smoke",
        )
        _, got = ticket.result(timeout=1800.0)
    finally:
        crypto_batch.set_min_tpu_batch(floor)
    need(ticket.backend == "tpu", ticket.backend)
    need(crypto_batch.LAST_ROUTE["path"] == "device", crypto_batch.LAST_ROUTE)
    check_dispatch(ed.LAST_DISPATCH, "xla", 1)
    compare_verdicts("commit", commit, bad, got, ref_all=True)

    # a window-sized batch straight to the kernel; time readiness
    batch, bad = corrupt(
        commit_lanes(src, chain_id, range(1, n_commits + 1)), seed, 5
    )
    got = timed_dispatch("verdicts[batch]", batch)
    check_dispatch(ed.LAST_DISPATCH, "xla", 1)
    compare_verdicts("batch", batch, bad, got, ref_all=False)


def timed_dispatch(name: str, items):
    """One dispatch straight to the kernel, its three waits on the
    host's clock: does ``wait()`` (block_until_ready) carry the device
    time, or does the fetch after it?"""
    from cometbft_tpu.ops import ed25519 as ed

    t0 = time.perf_counter()
    handle = ed.verify_batch_async(items)
    t1 = time.perf_counter()
    handle.wait()
    t2 = time.perf_counter()
    got = handle.result()
    t3 = time.perf_counter()
    say(
        f"{name}: dispatch lanes={ed.LAST_DISPATCH['lanes']} "
        f"prepare+enqueue_s={t1 - t0!r} wait_s={t2 - t1!r} "
        f"result_after_wait_s={t3 - t2!r}"
    )
    return got


# --- phase: pallas ------------------------------------------------------


def phase_pallas(src, chain_id: str, seed: int) -> None:
    """Every signature of the chain in one dispatch with the default
    ladder selection: the 65,536 bucket takes the Pallas ladder."""
    from cometbft_tpu.ops import ed25519 as ed

    n = src.block_store.height()
    items, bad = corrupt(commit_lanes(src, chain_id, range(1, n + 1)), seed, 5)
    t0 = time.perf_counter()
    got = ed.verify_batch(items)
    wall = time.perf_counter() - t0
    d = dict(ed.LAST_DISPATCH)
    say(f"pallas: signatures={len(items)} dispatch={d} first_call_s={wall!r}")
    check_dispatch(d, "pallas", 1)
    compare_verdicts("pallas", items, bad, got, ref_all=False)
    again = timed_dispatch("pallas[again]", items)  # compiled by now
    need((again == got).all(), "second pallas dispatch differs")


# --- phase: sharded (four chips) ----------------------------------------


def phase_sharded(
    src, chain_id: str, seed: int, n_commits: int, n_devices: int
) -> None:
    """The window-sized batch lane-sharded over every chip through the
    same ``verify_batch`` seam, then the psum quorum tally of
    parallel/sharded_verify, against the serial host verifier."""
    import jax.numpy as jnp
    import numpy as np

    from cometbft_tpu.ops import ed25519 as ed
    from cometbft_tpu.parallel.mesh import make_mesh
    from cometbft_tpu.parallel.sharded_verify import make_quorum_reducer

    batch, bad = corrupt(
        commit_lanes(src, chain_id, range(1, n_commits + 1)), seed, 5
    )
    probe = jnp.asarray(np.zeros(8, np.uint8))
    say(
        f"sharded: jnp.asarray(host array) lives on {sorted(map(str, probe.devices()))} "
        f"(what the shard_map program was handed before this PR: all of "
        f"it on the first device, resharded from there); verify_batch now "
        f"hands it the host arrays and its in_shardings place each shard"
    )
    t0 = time.perf_counter()
    handle = ed.verify_batch_async(batch)
    say(
        f"sharded: verdict lanes live on "
        f"{sorted(map(str, handle.wait()._res.devices()))} as "
        f"{handle._res.sharding}"
    )
    got = handle.result()
    wall = time.perf_counter() - t0
    d = dict(ed.LAST_DISPATCH)
    say(f"sharded: dispatch={d} wall_s={wall!r}")
    check_dispatch(d, "xla", n_devices)
    need(d["lanes"] % n_devices == 0, d)
    host = compare_verdicts("sharded", batch, bad, got, ref_all=False)

    # weighted tally + one psum over ICI + the 2/3 compare
    power = 10
    lanes = len(batch)
    need(lanes % n_devices == 0, lanes)
    total = power * lanes
    threshold = total * 2 // 3
    reducer = make_quorum_reducer(make_mesh(n_devices))
    quorum, tally, ok = reducer(
        np.asarray(got, bool),
        np.full(lanes, power, np.int32),
        np.int32(threshold),
    )
    want_tally = power * sum(host)
    say(
        f"sharded: quorum tally={int(tally)}/{total} threshold={threshold} "
        f"quorum={bool(quorum)} host_tally={want_tally} "
        f"tally lives on {sorted(map(str, tally.devices()))}"
    )
    need(int(tally) == want_tally == power * (lanes - len(bad)), int(tally))
    need(bool(quorum) == (want_tally > threshold), bool(quorum))
    need(np.array_equal(np.asarray(ok), np.asarray(got, bool)), "ok lanes")


# --- main ---------------------------------------------------------------


def find_tpu(chips: int):
    """The device dict of the result line, or None (and why, on
    stderr) when JAX finds no TPU or not ``chips`` of them. No option
    of the script relaxes this."""
    import jax

    devs = jax.devices()  # raises when the backend cannot start
    if devs[0].platform != "tpu":
        why = f"needs a TPU, JAX found platform {devs[0].platform!r}"
    elif len(devs) != chips:
        why = f"--chips {chips} but JAX sees {len(devs)} devices"
    else:
        return {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
    print(f"chip_smoke: {why}; nothing was run", file=sys.stderr)
    return None


def warm_up(compiles: CompileLog, src, chain_id: str) -> None:
    """Compile the run's one verify program: a commit through the
    kernel, as the phases will call it. Set-up, not a phase."""
    from cometbft_tpu.ops import ed25519 as ed

    t0 = time.perf_counter()
    warm = ed.verify_batch_async(commit_lanes(src, chain_id, [1]))
    need(warm.wait().result().all(), "warm-up did not verify")
    say(
        f"compile: warm-up wall_s={time.perf_counter() - t0!r} (set-up) "
        f"programs={compiles.programs} cache_hits={compiles.cache_hits}"
    )
    need(len(compiles.verify_programs()) == 1, compiles.programs)


def run(args, compiles: CompileLog) -> None:
    from cometbft_tpu.ops import ed25519 as ed
    from cometbft_tpu.ops import fe25519 as fe

    ed.PAD_MIN = PAD_MIN
    sharded = args.chips > 1
    if sharded:
        # one more whole-program compile at four times the charge: the
        # rolled field form compiles in about a minute, the tuple form
        # (the TPU default) in about a quarter of an hour
        fe.set_compact(True)
        n_blocks, why = BATCH_COMMITS + 1, "the commits of one batch + the tip"
    else:
        n_blocks, why = N_BLOCKS, f"three {WINDOW}-block verify windows + the tip"
    say(
        f"pad_min={PAD_MIN} field_mode="
        f"{'compact' if fe.compact_mode() else 'tuple'} ladder=xla programs=1"
    )
    say(
        f"chain: validators={N_VALS} blocks={n_blocks} seed={args.seed} "
        f"(cut: that deployment's chain is {FULL_LENGTH} blocks long; "
        f"{n_blocks} = {why})"
    )
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as home:
        t0 = time.perf_counter()
        gen, src = build_chain(args.seed, N_VALS, n_blocks, home)
        say(f"chain: built in {time.perf_counter() - t0!r} s (set-up)")
        warm_up(compiles, src, gen.chain_id)
        if sharded:
            phase_sharded(
                src, gen.chain_id, args.seed, BATCH_COMMITS, args.chips
            )
            say("catchup: not run\nverdicts: not run")
        else:
            with compiles.none_expected("catchup"):
                phase_catchup(gen, src, WINDOW)
            with compiles.none_expected("verdicts"):
                phase_verdicts(src, gen.chain_id, args.seed, BATCH_COMMITS)
        if args.pallas:
            phase_pallas(src, gen.chain_id, args.seed)
        else:
            say("pallas: not run")
    need(
        len(compiles.verify_programs()) == 1 + args.pallas, compiles.programs
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20240924)
    ap.add_argument(
        "--pallas", action="store_true",
        help="also run the 65,536-lane Pallas dispatch (a second compile)",
    )
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run only the lane-sharded verify + psum tally",
    )
    args = ap.parse_args(argv)
    if args.pallas and args.chips != 1:
        ap.error("--pallas is a one-chip phase")

    device = find_tpu(args.chips)
    if device is None:
        return 2
    import jax

    say(
        f"chip_smoke: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')}"
    )
    from cometbft_tpu.utils.device import setup_compile_cache

    cache_dir = setup_compile_cache()
    say(f"compile cache: dir={cache_dir} entries_before={cache_entries(cache_dir)}")
    compiles = CompileLog()
    run(args, compiles)
    say(
        f"compile cache: dir={cache_dir} entries_after={cache_entries(cache_dir)} "
        f"verify_programs={compiles.verify_programs()} "
        f"cache_hits={compiles.cache_hits}"
    )
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
