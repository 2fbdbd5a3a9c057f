"""bftlint (cometbft_tpu.analysis) tier-1 gate + unit fixtures.

Three layers:
  1. per-rule positive/negative fixtures (pure-ast, no jax import);
  2. the suppression / baseline / CLI machinery contracts;
  3. the repo gate: the full pass over cometbft_tpu/ must be clean
     against the checked-in baseline, and tools/lint.sh must pass —
     this is what ratchets every future PR.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from cometbft_tpu.analysis import analyze_source
from cometbft_tpu.analysis import baseline as baseline_mod
from cometbft_tpu.analysis.cli import main
from cometbft_tpu.analysis.findings import Finding
from cometbft_tpu.analysis.registry import (
    all_project_rules,
    all_rules,
    resolve,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


def ids_of(src: str, path: str = "x.py"):
    return sorted(
        {f.rule_id for f in analyze_source(textwrap.dedent(src), path)}
    )


# path-scoped rules need their fixtures analyzed under an in-scope
# path (ASY107 only applies inside the tracing plane, ASY109 inside
# the hot planes)
FIXTURE_PATHS = {
    "ASY107": "cometbft_tpu/trace/x.py",
    "ASY109": "cometbft_tpu/mempool/x.py",
    "ASY110": "cometbft_tpu/p2p/x.py",
    "ASY111": "cometbft_tpu/consensus/x.py",
    "ASY112": "cometbft_tpu/p2p/x.py",
    "ASY113": "cometbft_tpu/light/x.py",
    "ASY114": "cometbft_tpu/consensus/x.py",
    "ASY115": "cometbft_tpu/consensus/x.py",
    "ASY117": "cometbft_tpu/consensus/x.py",
    "ASY118": "cometbft_tpu/consensus/x.py",
    "ASY119": "cometbft_tpu/consensus/x.py",
    "ASY120": "cometbft_tpu/store/x.py",
    "ASY121": "cometbft_tpu/blocksync/x.py",
    "ASY122": "cometbft_tpu/fleet/x.py",
    "ASY123": "cometbft_tpu/state/x.py",
}


# --- 1. rule fixtures -------------------------------------------------
#
# (rule_id, positive fixture that MUST flag, negative fixture that
# MUST stay clean for that rule)

FIXTURES = [
    (
        "ASY101",  # blocking-call-in-async
        """
        import time
        async def f():
            time.sleep(1.0)
        """,
        """
        import asyncio, time
        async def f():
            await asyncio.sleep(1.0)
            await asyncio.to_thread(time.sleep, 1.0)
        def g():
            time.sleep(1.0)  # sync context: fine
        """,
    ),
    (
        "ASY102",  # unawaited-coroutine
        """
        import asyncio
        async def f():
            asyncio.sleep(1.0)
        """,
        """
        import asyncio
        async def f():
            await asyncio.sleep(1.0)
            t = asyncio.sleep(1.0)
            await t
        """,
    ),
    (
        "ASY102",  # unawaited self-method coroutine
        """
        class R:
            async def pump(self):
                pass
            async def run(self):
                self.pump()
        """,
        """
        class R:
            async def pump(self):
                pass
            async def run(self):
                await self.pump()
                # chained receiver: target object unknown, not flagged
                self.pool.pump()
        """,
    ),
    (
        "ASY103",  # dropped-task
        """
        import asyncio
        async def f(coro):
            asyncio.create_task(coro)
        """,
        """
        import asyncio
        from cometbft_tpu.utils.tasks import spawn
        async def f(coro):
            t = asyncio.create_task(coro)
            spawn(coro)
            return t
        """,
    ),
    (
        "ASY104",  # broad-except-in-async: bare except over await
        """
        async def f(x):
            try:
                await x()
            except Exception:
                pass
        """,
        """
        import asyncio
        async def f(x):
            try:
                await x()
            except asyncio.CancelledError:
                raise
            except Exception:
                pass
        """,
    ),
    (
        "ASY104",  # tuple spelling still swallows CancelledError
        """
        import asyncio
        async def f(t):
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        """,
        """
        async def f(x):
            try:
                y = x + 1   # no await in try body: not our concern
            except Exception:
                y = 0
            return y
        """,
    ),
    (
        "ASY105",  # sync-lock-across-await
        """
        import asyncio
        async def f(self):
            with self._lock:
                await asyncio.sleep(0)
        """,
        """
        import asyncio
        async def f(self):
            async with self._lock:
                await asyncio.sleep(0)
            with self._lock:
                self.n += 1   # no await while held: fine
        """,
    ),
    (
        "ASY106",  # nested-event-loop
        """
        import asyncio
        async def f(coro):
            asyncio.run(coro)
        """,
        """
        import asyncio
        def cli(coro):
            asyncio.run(coro)   # sync entry point: fine
        """,
    ),
    (
        "JAX201",  # host-sync-in-jit
        """
        import jax
        @jax.jit
        def f(x):
            return x.sum().item()
        """,
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            n = int(x.shape[0])   # static metadata: fine
            return jnp.sum(x) + n
        def host(x):
            return x.sum().item()  # not jitted: fine
        """,
    ),
    (
        "JAX201",  # the `return jax.jit(core)` factory idiom is seen
        """
        import jax, numpy as np
        def make():
            def core(x):
                return np.asarray(x)
            return jax.jit(core)
        """,
        """
        import jax
        import jax.numpy as jnp
        def make():
            def core(x):
                return jnp.asarray(x)   # device-side: fine
            return jax.jit(core)
        """,
    ),
    (
        "JAX202",  # stray-block-until-ready
        """
        def f(res):
            res.block_until_ready()
        """,
        """
        def f(res):
            return res
        """,
    ),
    (
        "JAX203",  # traced-loop
        """
        import jax
        @jax.jit
        def f(x):
            s = 0.0
            for v in x:
                s = s + v
            return s
        """,
        """
        import jax
        @jax.jit
        def f(x, n):
            s = 0.0
            for i in range(4):      # static trip count: fine
                s = s + x[i]
            for j, w in enumerate((1, 2)):   # static pytree: fine
                s = s + w
            return s
        """,
    ),
    (
        "JAX204",  # per-call-jit
        """
        import jax
        def f(xs, g):
            out = []
            for x in xs:
                out.append(jax.jit(g)(x))
            return out
        """,
        """
        import jax
        def make(g):
            return jax.jit(g)   # bound once by the caller: fine
        """,
    ),
    (
        "ASY107",  # wallclock-in-trace (path-scoped: FIXTURE_PATHS)
        """
        import time
        def stamp():
            return time.time_ns()
        """,
        """
        import time
        def stamp():
            return time.monotonic_ns()
        def also_fine():
            return time.perf_counter()
        """,
    ),
    (
        "ASY108",  # sync-abci-in-receive
        """
        class MempoolishReactor(Reactor):
            def receive(self, chan_id, peer, msg):
                self.mempool.check_tx(msg, sender=peer.peer_id)
        class ServingReactor:
            def receive(self, chan_id, peer, msg):
                chunk = self.proxy.snapshot.load_snapshot_chunk(1, 0, 0)
        """,
        """
        class GoodReactor(Reactor):
            def receive(self, chan_id, peer, msg):
                self.ingest.submit_nowait(msg, sender=peer.peer_id)
                n = self.mempool.size()   # not an ABCI call: fine
        class NotAReactorClass:
            def receive(self, chan_id, peer, msg):
                self.mempool.check_tx(msg)  # not a reactor: fine
        class OtherReactor(Reactor):
            def add_peer(self, peer):
                self.proxy.info(None)  # not receive(): other rules' job
        """,
    ),
    (
        "ASY109",  # unbounded-queue-in-hot-plane (FIXTURE_PATHS)
        """
        import asyncio
        def build():
            a = asyncio.Queue()
            b = asyncio.Queue(maxsize=0)
            c = InstrumentedQueue(name="x")
            return a, b, c
        """,
        """
        import asyncio, queue
        def build():
            a = asyncio.Queue(100)
            b = asyncio.Queue(maxsize=256)
            c = InstrumentedQueue(512, name="x")
            d = queue.Queue()      # sync stdlib queue: not this rule
            e = Queue()            # ambiguous bare spelling: not ours
            return a, b, c, d, e
        """,
    ),
    (
        "ASY110",  # unbounded-await-in-stop (FIXTURE_PATHS)
        """
        import asyncio
        class Plane:
            async def stop(self):
                await self.inner.stop()
            async def close(self):
                await self.task
        """,
        """
        import asyncio
        class Plane:
            async def stop(self):
                await self._halt(True)          # covered delegation
                await asyncio.sleep(0.1)
            async def _halt(self, graceful):
                try:
                    await asyncio.wait_for(self.task, 5.0)
                except asyncio.TimeoutError:
                    pass
                await guard.stage("x", self.inner.stop())
                await asyncio.wait({self.task}, timeout=1.0)
            async def run(self):
                await self.inner.stop()         # not a stop path
        """,
    ),
    (
        "ASY112",  # finite-reconnect-give-up (FIXTURE_PATHS)
        """
        import asyncio
        class Switch:
            async def _reconnect_routine(self, peer_id, addr):
                for _ in range(20):
                    await asyncio.sleep(1.0)
                    try:
                        await self.dial_peer(addr, peer_id)
                        return
                    except asyncio.CancelledError:
                        raise
                    except Exception:
                        continue
        """,
        """
        import asyncio
        class Plane:
            async def _fast_routine(self, peer_id):
                attempt = 0
                while attempt < self.fast_attempts:
                    await asyncio.sleep(0.1)
                    attempt += 1
                    if await self._try_dial(peer_id):
                        return
                # budget spent = LANE TRANSITION, not a give-up
                self._park_slow_lane(peer_id)
            async def crawl(self):
                # iterating candidate ADDRESSES, not a retry budget
                for addr in self.book.pick_to_dial(set(), 3):
                    await self.dial_peer(addr)
            async def sweep(self):
                while True:
                    await asyncio.sleep(30.0)
                    await self.dial_peer("a@b:1")
        """,
    ),
    (
        "ASY111",  # direct-fsync-in-hot-plane (FIXTURE_PATHS)
        """
        import os
        def persist(f):
            f.flush()
            os.fsync(f.fileno())
        """,
        """
        def persist(self, msg):
            # barriers route through the WAL group-commit seam
            self.wal.write_sync(msg)
            return self.wal.write_group(msg)
        """,
    ),
    (
        "ASY113",  # uncoalesced-verify-in-light (FIXTURE_PATHS)
        """
        from .. import types as T
        def check(chain_id, vals, block_id, height, commit):
            T.verify_commit_light(
                chain_id, vals, block_id, height, commit
            )
            T.verify_commit_light_trusting(
                chain_id, vals, commit, cache=None
            )
        """,
        """
        from .. import types as T
        def check(self, chain_id, vals, block_id, height, commit):
            T.verify_commit_light(
                chain_id, vals, block_id, height, commit,
                cache=self.cache,
            )
            self.engine.verify_commit_light(
                vals, block_id, height, commit
            )
            engine.verify_commit_light_trusting(
                vals, commit, level
            )
        """,
    ),
    (
        "ASY114",  # transitive-blocking-call (interprocedural;
        # FIXTURE_PATHS — hot plane): the blocking leaf hides TWO
        # frames down a self.<attr>.<method> chain the attribute-type
        # inference must resolve
        """
        import time
        class Pool:
            def drain(self):
                self._wait()
            def _wait(self):
                time.sleep(0.5)
        class Reactor:
            def __init__(self):
                self.pool = Pool()
            async def run(self):
                self.pool.drain()
        """,
        """
        import asyncio, time
        class Pool:
            def drain(self):
                time.sleep(0.5)
        class Reactor:
            def __init__(self):
                self.pool = Pool()
            async def run(self):
                # a function REFERENCE passed to the offload seam is
                # an argument, not a call: no edge, no finding
                await asyncio.to_thread(self.pool.drain)
            def sync_entry(self):
                self.pool.drain()   # sync context: fine
        """,
    ),
    (
        "ASY115",  # await-holding-lock (interprocedural)
        """
        import os, threading
        class W:
            def __init__(self):
                self._lock = threading.Lock()
            def _barrier(self, f):
                os.fsync(f.fileno())
            def persist(self, f):
                with self._lock:
                    self._barrier(f)
        """,
        """
        import os, threading
        class W:
            def __init__(self):
                self._lock = threading.Lock()
            def _barrier(self, f):
                os.fsync(f.fileno())  # bftlint: disable=ASY111
            def persist(self, f):
                with self._lock:
                    f.write(b"x")
                self._barrier(f)   # outside the critical section
        """,
    ),
    (
        "ASY116",  # sync-listener-blocking-call (interprocedural):
        # the pre-ISSUE-15 indexer shape — a bus sync listener whose
        # chain ends in a DB batch write runs INSIDE every publish,
        # so the consensus finalize path pays the disk write
        """
        class Indexer:
            def __init__(self, db):
                self.db = db
            def index(self, e):
                self.db.write_batch([(b"k", b"v")])
        class Service:
            def __init__(self, bus, idx: Indexer):
                self.idx = idx
                bus.add_sync_listener(idx.index)
        """,
        """
        import asyncio
        class Indexer:
            def __init__(self, db):
                self.db = db
            def flush(self, bundle):
                self.db.write_batch(bundle)
        class Service:
            def __init__(self, bus, idx: Indexer):
                self.idx = idx
                self.pending = []
                bus.add_sync_listener(self.on_event)
            def on_event(self, e):
                # accumulate-only: the listener never touches the DB
                self.pending.append(e)
            async def drain(self):
                # the flush is OFFLOADED — a function reference is an
                # argument, not a call: no edge, no finding
                await asyncio.to_thread(self.idx.flush, self.pending)
        """,
    ),
    (
        "ASY117",  # superlinear-msg-handler (interprocedural): the
        # per-message receive path reaches a validators-domain loop
        # two hops down — O(V) per message, O(V^2) per height
        """
        class Reactor:
            def __init__(self, validators):
                self.validators = validators
            def receive(self, msg, peer):
                self._tally(msg)
            def _tally(self, msg):
                total = 0
                for v in self.validators:
                    total += v.voting_power
        """,
        """
        class Reactor:
            def __init__(self, validators):
                self.by_addr = {v.address: v for v in validators}
                self.total = 0
            def receive(self, msg, peer):
                # incremental: one dict lookup + a running sum, no
                # committee loop on the per-message path
                val = self.by_addr.get(msg.address)
                if val is not None:
                    self.total += val.voting_power
            def rebuild(self, validators):
                # membership change, not per-message: loop is fine
                self.by_addr = {v.address: v for v in validators}
        """,
    ),
    (
        "ASY118",  # nested-committee-loop: validator x validator is
        # the direct quadratic (the update_with_change_set shape
        # this PR fixed with a one-pass address index)
        """
        from typing import Sequence
        def update(validators, changes: Sequence[Validator]):
            out = []
            updates = [c for c in changes if c.power > 0]
            for v in validators:
                for c in updates:
                    if c.address == v.address:
                        out.append(c)
            return out
        """,
        """
        from typing import Sequence
        def update(validators, changes: Sequence[Validator]):
            by_addr = {c.address: c for c in changes}  # index once
            out = []
            for v in validators:
                c = by_addr.get(v.address)
                if c is not None:
                    out.append(c)
            return out
        def retries(validators):
            # committee x constant: bounded inner loop, not nesting
            for v in validators:
                for attempt in range(3):
                    pass
        """,
    ),
    (
        "ASY119",  # unbounded-growth-in-hot-plane: a container attr
        # fed by the per-message path with no prune anywhere is the
        # months-horizon soak leak
        """
        class Reactor:
            def __init__(self):
                self.seen = set()
            def receive(self, msg, peer):
                self.seen.add(msg.key())
        """,
        """
        class Reactor:
            def __init__(self):
                self.seen = set()
            def receive(self, msg, peer):
                self.seen.add(msg.key())
            def advance_height(self):
                self.seen.clear()  # pruned on height advance
        """,
    ),
    (
        "ASY120",  # unbounded-delete-in-hot-plane: a DB-scan loop
        # deleting one row per iteration — unbounded trip count and
        # no crash-consistency marker (the shape the retention
        # plane's sliced write_batch discipline replaces)
        """
        def prune(db, prefix):
            for k, v in db.iter_prefix(prefix):
                db.delete(k)
        """,
        """
        def prune(db, prefix, marker, enc):
            # sanctioned: collect doomed keys, ONE atomic batch with
            # the base-marker advance riding along
            doomed = [k for k, _ in db.iter_prefix(prefix)]
            db.write_batch([(marker, enc)], doomed)
        def drop_bounded(db, doomed):
            # bounded plain-list loop: not scan-driven, fine
            for k in doomed:
                db.delete(k)
        """,
    ),
    (
        "ASY121",  # verify-bypass-scheduler: a hot plane building the
        # serial verifier / touching the parallel-verify pool directly
        # verifies outside the scheduler's priority classes
        """
        from cometbft_tpu.crypto.batch import CpuBatchVerifier
        from cometbft_tpu.crypto import batch, parallel_verify
        def window_verify(jobs):
            v = CpuBatchVerifier()
            for pk, msg, sig in jobs:
                v.add(pk, msg, sig)
            return v.verify()
        def module_verify(jobs):
            return batch.CpuBatchVerifier()
        def pool_verify(items):
            return parallel_verify.engine().verify(items)
        """,
        """
        from cometbft_tpu.crypto import scheduler as crypto_sched
        from cometbft_tpu.crypto.parallel_verify import (
            dispatch_stats_if_running,
        )
        from cometbft_tpu.crypto import parallel_verify
        def window_verify(jobs):
            # sanctioned: the unified scheduler's priority classes
            t = crypto_sched.scheduler().submit(
                jobs, priority=crypto_sched.PRIORITY_CATCHUP
            )
            return t.result()
        def gauges():
            # stats reads are not verification
            return parallel_verify.dispatch_stats_if_running()
        """,
    ),
    (
        "ASY122",  # serve-bypass-router: fleet code serving off a
        # replica's plane directly skips gate admission, consistency
        # tokens and lag/failover accounting
        """
        def handle_light(replica, height):
            s = replica.light_plane.open_session()
            return s.verified_block(height)
        def warm(replica, cache, height, fn):
            cache.get_or_verify(height, fn)
            return replica.light_plane.serve(height)
        """,
        """
        def handle_light(router, height, token):
            # sanctioned: the router seam admits, tokens and counts
            return router.serve_light(height, token)
        def rotate_out(replica):
            # plane lifecycle is not serving
            replica.light_plane.drain(5.0)
            replica.light_plane.resume()
            return replica.light_plane.stats()
        """,
    ),
    (
        "ASY123",  # per-item-hash-in-finalize-path: a for-loop
        # hashing per tx reached from a finalize phase root — the
        # host overhead the native finalize lane batches away
        """
        import hashlib
        class Exec:
            def apply_block(self, state, block):
                resp = self.proxy.finalize_block(block)
                self._persist(block, resp)
            def _persist(self, block, resp):
                hashes = []
                for tx in block.txs:
                    hashes.append(hashlib.sha256(tx).digest())
                self.store.save(block.height, hashes)
        """,
        """
        import hashlib
        from cometbft_tpu.state import native_finalize
        class Exec:
            def apply_block(self, state, block):
                resp = self.proxy.finalize_block(block)
                # sanctioned shape: ONE batched native pass, the
                # artifacts carry every per-item derivation
                arts = native_finalize.finalize_pass(block.txs, resp)
                self._persist(block, arts)
            def _persist(self, block, arts):
                self.store.save(block.height, arts.results_hash)
            def decode_rows(self, rows):
                # not finalize-reachable: per-item work off the
                # apply path is out of scope
                return [hashlib.sha256(r).digest() for r in rows]
        """,
    ),
    (
        "SYN000",  # syntax errors are findings, not crashes
        """
        def f(:
        """,
        """
        def f():
            return 1
        """,
    ),
]


@pytest.mark.parametrize(
    "rule_id,bad,good",
    FIXTURES,
    ids=[f"{r}-{i}" for i, (r, _, _) in enumerate(FIXTURES)],
)
def test_rule_fixture(rule_id, bad, good):
    path = FIXTURE_PATHS.get(rule_id, "x.py")
    assert rule_id in ids_of(bad, path), (
        f"{rule_id} missed its positive"
    )
    assert rule_id not in ids_of(good, path), (
        f"{rule_id} false-positived on its negative"
    )


def test_asy109_scoped_to_hot_planes():
    src = """
    import asyncio
    def f():
        return asyncio.Queue()
    """
    # tools / tests / utils are out of scope: an unbounded queue in a
    # CLI helper is not a hot-plane OOM hazard
    assert "ASY109" not in ids_of(src)
    assert "ASY109" not in ids_of(src, "cometbft_tpu/utils/x.py")
    for pkg in ("p2p", "consensus", "types", "obs", "rpc"):
        assert "ASY109" in ids_of(src, f"cometbft_tpu/{pkg}/x.py"), pkg


def test_asy107_scoped_to_trace_package():
    src = """
    import time
    def stamp():
        return time.time()
    """
    assert "ASY107" not in ids_of(src)  # outside the plane: fine
    assert "ASY107" in ids_of(src, "cometbft_tpu/trace/export.py")


def test_asy116_sanctioned_registration():
    """A justified suppression at the registration line is the
    escape hatch (state/indexer.py start(): the only blocking reach
    is the no-loop inline degrade)."""
    src = textwrap.dedent(
        """
        class Indexer:
            def __init__(self, db):
                self.db = db
            def index(self, e):
                self.db.write_batch([(b"k", b"v")])
        class Service:
            def __init__(self, bus, idx: Indexer):
                self.idx = idx
                bus.add_sync_listener(idx.index)  # bftlint: disable=ASY116
        """
    )
    assert "ASY116" not in ids_of(src)


def test_asy116_repo_indexer_shape_stays_clean():
    """The shipped IndexerService accumulates in memory — the one
    suppression in state/indexer.py must remain the ONLY one needed
    (the whole-repo gate below enforces zero new findings, this
    pins the specific rule)."""
    from cometbft_tpu.analysis.engine import REPO_ROOT, run

    findings = [
        f
        for f in run([str(REPO_ROOT / "cometbft_tpu" / "state")])
        if f.rule_id == "ASY116"
    ]
    assert findings == [], findings


def test_at_least_eight_distinct_rules_have_fixtures():
    covered = {r for r, _, _ in FIXTURES if r != "SYN000"}
    assert len(covered) >= 8, covered


def test_every_registered_rule_has_a_fixture():
    registered = {r.rule_id for r in all_rules()} | {
        pr.rule_id for pr in all_project_rules()
    }
    covered = {r for r, _, _ in FIXTURES}
    assert registered <= covered, registered - covered


# --- 2a. suppression machinery ---------------------------------------

TWO_RULES_ONE_LINE = """
import time
async def f(loop):
    loop.run_until_complete(time.sleep(1)){}
"""


def test_disable_silences_only_named_rule_on_that_line():
    # the line triggers BOTH ASY101 (time.sleep in async) and ASY106
    # (run_until_complete in async)
    base = ids_of(TWO_RULES_ONE_LINE.format(""))
    assert {"ASY101", "ASY106"} <= set(base)
    got = ids_of(
        TWO_RULES_ONE_LINE.format("  # bftlint: disable=ASY106")
    )
    assert "ASY106" not in got and "ASY101" in got


def test_disable_does_not_leak_to_other_lines():
    src = """
    import time
    async def f():
        time.sleep(1)  # bftlint: disable=ASY101
        time.sleep(2)
    """
    found = analyze_source(textwrap.dedent(src), "x.py")
    lines = [f.line for f in found if f.rule_id == "ASY101"]
    assert lines == [5]


def test_disable_by_rule_name_and_disable_next():
    src = """
    import time
    async def f():
        # bftlint: disable-next=blocking-call-in-async
        time.sleep(1)
    """
    assert "ASY101" not in ids_of(src)


def test_disable_file_silences_whole_file_one_rule_only():
    src = """
    # bftlint: disable-file=ASY101
    import asyncio, time
    async def f():
        time.sleep(1)
        asyncio.sleep(2)
    """
    got = ids_of(src)
    assert "ASY101" not in got and "ASY102" in got


def test_unknown_suppression_is_reported():
    src = """
    def f():
        return 1  # bftlint: disable=NOPE999
    """
    assert "SUP001" in ids_of(src)


def test_resolve_accepts_id_and_name():
    assert resolve("ASY101") == "ASY101"
    assert resolve("blocking-call-in-async") == "ASY101"
    assert resolve("nope") is None


# --- 2b. baseline machinery ------------------------------------------


def _f(path, line, rule="ASY104"):
    return Finding(path, line, 0, rule, "broad-except-in-async", "m")


def test_baseline_roundtrip(tmp_path):
    entries = baseline_mod.build([_f("a.py", 1), _f("a.py", 9)])
    p = tmp_path / "b.json"
    baseline_mod.save(str(p), entries)
    assert baseline_mod.load(str(p)) == {"a.py": {"ASY104": 2}}


def test_baseline_exact_count_is_clean_and_over_is_new():
    bl = {"a.py": {"ASY104": 2}}
    new, stale = baseline_mod.apply([_f("a.py", 1), _f("a.py", 9)], bl)
    assert new == [] and stale == []
    new, stale = baseline_mod.apply(
        [_f("a.py", 1), _f("a.py", 9), _f("a.py", 30)], bl
    )
    assert len(new) == 3  # count exceeded: all reported (can't tell
    assert stale == []    # old from new by line)


def test_stale_baseline_entries_are_reported():
    bl = {"a.py": {"ASY104": 2}, "gone.py": {"ASY101": 1}}
    new, stale = baseline_mod.apply([_f("a.py", 1)], bl)
    assert new == []
    got = {(s.path, s.rule_id, s.allowed, s.current) for s in stale}
    assert got == {("a.py", "ASY104", 2, 1), ("gone.py", "ASY101", 1, 0)}


# --- 2c. CLI exit-code contract --------------------------------------

CLEAN = "def f():\n    return 1\n"
DIRTY = "import time\nasync def f():\n    time.sleep(1)\n"


def test_cli_exit_zero_on_clean(tmp_path, capsys):
    p = tmp_path / "ok.py"
    p.write_text(CLEAN)
    assert main([str(p), "--no-baseline"]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_exit_one_on_violation(tmp_path, capsys):
    p = tmp_path / "bad.py"
    p.write_text(DIRTY)
    assert main([str(p), "--no-baseline"]) == 1
    assert "ASY101" in capsys.readouterr().out


def test_cli_baseline_covers_violation(tmp_path, capsys):
    p = tmp_path / "bad.py"
    p.write_text(DIRTY)
    bl = tmp_path / "bl.json"
    assert main([str(p), "--baseline", str(bl),
                 "--update-baseline"]) == 0
    capsys.readouterr()
    assert main([str(p), "--baseline", str(bl)]) == 0


def test_cli_stale_reported_and_fail_on_stale(tmp_path, capsys):
    p = tmp_path / "ok.py"
    p.write_text(CLEAN)
    bl = tmp_path / "bl.json"
    baseline_mod.save(str(bl), {"nothere.py": {"ASY101": 1}})
    assert main([str(p), "--baseline", str(bl)]) == 0
    assert "stale baseline" in capsys.readouterr().out
    assert main([str(p), "--baseline", str(bl),
                 "--fail-on-stale"]) == 1


def test_cli_json_format(tmp_path, capsys):
    p = tmp_path / "bad.py"
    p.write_text(DIRTY)
    assert main([str(p), "--no-baseline", "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["findings"][0]["rule_id"] == "ASY101"


def test_cli_syntax_error_fails(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    assert main([str(p), "--no-baseline"]) == 1


def test_cli_nonexistent_path_is_usage_error(tmp_path, capsys):
    """A typo'd path must not report 'clean' (exit 0) — and must
    never reach --update-baseline, which would wipe the baseline."""
    ghost = str(tmp_path / "no_such_dir")
    assert main([ghost, "--no-baseline"]) == 2
    bl = tmp_path / "bl.json"
    baseline_mod.save(str(bl), {"a.py": {"ASY104": 1}})
    assert main([ghost, "--baseline", str(bl),
                 "--update-baseline"]) == 2
    assert baseline_mod.load(str(bl)) == {"a.py": {"ASY104": 1}}


def test_lockish_does_not_match_block_identifiers():
    """'lock' must be a name segment, not a substring: block_store /
    unblock are not locks (regression: blockchain codebase!)."""
    src = """
    import asyncio
    async def f(self):
        with self.block_writer():
            await asyncio.sleep(0)
    """
    assert "ASY105" not in ids_of(src)
    src2 = """
    import asyncio
    async def f(self):
        with self.state_lock:
            await asyncio.sleep(0)
    """
    assert "ASY105" in ids_of(src2)


def test_jit_wrap_invoke_in_loop_reports_once():
    src = """
    import jax
    def f(xs, g):
        for x in xs:
            y = jax.jit(g)(x)
        return y
    """
    found = [
        f for f in analyze_source(textwrap.dedent(src), "x.py")
        if f.rule_id == "JAX204"
    ]
    assert len(found) == 1, found


# --- 2d. call graph (interprocedural model) ---------------------------

import ast as _ast

from cometbft_tpu.analysis.callgraph import Project


def _proj(**files):
    """Project from {filename_stem: source}; stems become
    cometbft_tpu/consensus/<stem>.py so hot-plane rules apply."""
    return Project(
        [
            (f"cometbft_tpu/consensus/{k}.py",
             _ast.parse(textwrap.dedent(v)))
            for k, v in files.items()
        ]
    )


def _chain(p, qual):
    return p.blocking_chain(qual)


def test_callgraph_cycles_terminate():
    p = _proj(m="""
        import time
        def a():
            b()
        def b():
            a()
            time.sleep(1)
        def pure_cycle_x():
            pure_cycle_y()
        def pure_cycle_y():
            pure_cycle_x()
    """)
    f = "cometbft_tpu/consensus/m.py"
    # a -> b -> sleep (the b->a back-edge contributes nothing)
    assert _chain(p, f + "::a") == ["b", "time.sleep"]
    # a pure cycle has no chain and does not hang
    assert _chain(p, f + "::pure_cycle_x") is None


def test_callgraph_inheritance_and_super_dispatch():
    p = _proj(m="""
        import time
        class Base:
            def helper(self):
                time.sleep(1)
            def stop(self):
                self.helper()
        class Child(Base):
            def stop(self):
                super().stop()
        class GrandChild(Child):
            def run(self):
                self.helper()   # two levels up the chain
    """)
    f = "cometbft_tpu/consensus/m.py"
    assert _chain(p, f + "::Child.stop") == [
        "super().stop", "self.helper", "time.sleep"
    ]
    assert _chain(p, f + "::GrandChild.run") == [
        "self.helper", "time.sleep"
    ]


def test_callgraph_decorated_defs_still_resolve():
    p = _proj(m="""
        import functools, time
        def deco(fn):
            return fn
        @deco
        def helper():
            time.sleep(1)
        @functools.lru_cache(maxsize=None)
        def cached_helper():
            helper()
        def entry():
            cached_helper()
    """)
    f = "cometbft_tpu/consensus/m.py"
    assert _chain(p, f + "::entry") == [
        "cached_helper", "helper", "time.sleep"
    ]


def test_callgraph_functools_partial_edge():
    p = _proj(m="""
        import functools, time
        def helper(x):
            time.sleep(x)
        def entry():
            functools.partial(helper, 1)()
        def entry2(run):
            run(functools.partial(helper, 2))
    """)
    f = "cometbft_tpu/consensus/m.py"
    # partial(f, ...) creates the edge to f in both shapes
    assert _chain(p, f + "::entry") == ["helper", "time.sleep"]
    assert _chain(p, f + "::entry2") == ["helper", "time.sleep"]


def test_callgraph_lambda_callees_attributed_to_enclosing():
    p = _proj(m="""
        import time
        def helper():
            time.sleep(1)
        def entry(xs):
            return sorted(xs, key=lambda x: helper())
    """)
    f = "cometbft_tpu/consensus/m.py"
    assert _chain(p, f + "::entry") == ["helper", "time.sleep"]


def test_callgraph_attr_types_from_init_and_annotations():
    p = _proj(m="""
        class Wal:
            async def flush(self):
                pass
        class Pool:
            def __init__(self):
                self.inner = Wal()
        class CS:
            def __init__(self, wal: Wal):
                self.wal = wal
                self.pool = Pool()
    """)
    f = "cometbft_tpu/consensus/m.py"
    cs = p.module_classes[f]["CS"]
    assert cs.attr_types == {"wal": "Wal", "pool": "Pool"}
    pool = p.module_classes[f]["Pool"]
    assert pool.attr_types == {"inner": "Wal"}


def test_asy102_deep_chain_via_inferred_types():
    src = """
    class Pool:
        async def stop(self):
            pass
    class R:
        def __init__(self):
            self.pool = Pool()
        async def shutdown(self):
            self.pool.stop()
    """
    assert "ASY102" in ids_of(src)
    good = """
    class Pool:
        async def stop(self):
            pass
    class R:
        def __init__(self):
            self.pool = Pool()
        async def shutdown(self):
            await self.pool.stop()
        async def unknown_attr(self):
            self.other.stop()   # untyped attr: under-approximate
    """
    assert "ASY102" not in ids_of(good)


def test_asy114_reports_the_full_chain_in_message():
    src = textwrap.dedent("""
    import time
    class Pool:
        def drain(self):
            self._wait()
        def _wait(self):
            time.sleep(0.5)
    class Reactor:
        def __init__(self):
            self.pool = Pool()
        async def run(self):
            self.pool.drain()
    """)
    found = [
        f for f in analyze_source(src, "cometbft_tpu/consensus/x.py")
        if f.rule_id == "ASY114"
    ]
    assert len(found) == 1
    msg = found[0].message
    assert "self.pool.drain" in msg and "time.sleep" in msg


def test_sanctioned_leaf_suppression_kills_chains():
    """A blocking leaf line suppressed for ASY114 in its own file is
    a sanctioned sink: chains through it vanish for ASY114 AND
    ASY115 (the WAL-seam escape hatch)."""
    src = """
    import os, threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
        def _barrier(self, f):
            os.fsync(f.fileno())  # bftlint: disable=ASY114,ASY111
        def persist(self, f):
            with self._lock:
                self._barrier(f)
        async def apersist(self, f):
            self._barrier(f)
    """
    got = ids_of(src, "cometbft_tpu/consensus/x.py")
    assert "ASY114" not in got and "ASY115" not in got
    # the DIRECT-leaf-inside-the-lock shape honors the same sanction
    # (the WAL rotation barrier's exact form)
    direct = """
    import os, threading
    class W:
        def __init__(self):
            self._lock = threading.Lock()
        def persist(self, f):
            with self._lock:
                os.fsync(f.fileno())  # bftlint: disable=ASY114,ASY111
    """
    assert "ASY115" not in ids_of(direct, "cometbft_tpu/consensus/x.py")


def test_asy114_scoped_to_hot_planes():
    src = """
    import time
    def helper():
        time.sleep(1)
    async def f():
        helper()
    """
    assert "ASY114" in ids_of(src, "cometbft_tpu/consensus/x.py")
    assert "ASY114" in ids_of(src, "cometbft_tpu/node/x.py")
    # chaos/ is the injection harness; tools are out of scope
    assert "ASY114" not in ids_of(src, "cometbft_tpu/chaos/x.py")
    assert "ASY114" not in ids_of(src, "x.py")


def test_asy115_async_lock_flavor():
    src = """
    import time, asyncio
    class W:
        def _grind(self):
            time.sleep(0.1)
        async def hot(self):
            async with self._lock:
                self._grind()
    """
    assert "ASY115" in ids_of(src, "cometbft_tpu/consensus/x.py")


# --- 3. the repo gate -------------------------------------------------


def test_full_tree_is_clean_against_checked_in_baseline(capsys):
    """Every future PR runs this: the shipped tree must lint clean
    (new violations either fixed or explicitly baselined)."""
    rc = main([str(REPO_ROOT / "cometbft_tpu")])
    out = capsys.readouterr().out
    assert rc == 0, f"bftlint regressions:\n{out}"


def test_seeded_violation_fixture_fails_the_gate(tmp_path):
    """End-to-end: a fresh violation exits non-zero via the real CLI."""
    bad = tmp_path / "seeded.py"
    bad.write_text(
        "import asyncio, time\n"
        "async def reactor():\n"
        "    time.sleep(0.5)\n"
        "    asyncio.create_task(reactor())\n"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "cometbft_tpu.analysis", str(bad)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "ASY101" in proc.stdout and "ASY103" in proc.stdout


def test_lint_sh_entry_point():
    """tools/lint.sh = compileall syntax gate + the analysis pass
    (with --fail-on-stale so a shrinking baseline can never rot, and
    --timings so the interprocedural pass's cost stays visible)."""
    proc = subprocess.run(
        ["bash", str(REPO_ROOT / "tools" / "lint.sh")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "rule timings" in proc.stdout
    assert "ASY114*" in proc.stdout  # project rules are timed too


def test_shipped_baseline_is_empty():
    """ISSUE 14 burned the ASY104 baseline to zero: every violation
    fixed, none baselined. The ratchet now starts from nothing — any
    new violation anywhere fails the gate outright."""
    doc = json.loads(
        (REPO_ROOT / "tools" / "bftlint_baseline.json").read_text()
    )
    assert doc["entries"] == {}


def test_whole_repo_pass_stays_under_budget():
    """Acceptance: the full interprocedural run must stay under 15s
    on the 2-vCPU box (it is ~5s today; this guards the growth
    curve). Wall-clock, generous to suite contention."""
    import time as _t

    t0 = _t.perf_counter()
    rc = main([str(REPO_ROOT / "cometbft_tpu")])
    wall = _t.perf_counter() - t0
    assert rc == 0
    assert wall < 15.0, f"bftlint took {wall:.1f}s (budget 15s)"
