"""In-process node construction + local test networks.

The reference's consensus test fixtures (consensus/common_test.go
randConsensusNet) as a first-class module: build N fully-wired
consensus nodes around local ABCI apps and connect them with in-memory
message delivery — deterministic multi-node consensus on one host, no
sockets. Also the assembly core reused by the real networked node.
"""

from __future__ import annotations

import asyncio
import os
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from .. import types as T
from ..abci.client import AppConns
from ..config import Config, ConsensusConfig
from ..config.config import test_config
from ..consensus import ConsensusState, Handshaker
from ..crypto.keys import Ed25519PrivKey
from ..mempool import CListMempool
from ..models.kvstore import KVStoreApplication
from ..privval import FilePV
from ..state.execution import BlockExecutor
from ..state.store import Store as StateStore
from ..state.state_types import State
from ..store import BlockStore
from ..trace import NOOP as TRACE_NOOP
from ..trace import Tracer, enable_global
from ..types import events as ev
from ..types.genesis import GenesisDoc
from ..utils import kv


def record_clock_anchor(tracer) -> None:
    """Stamp a monotonic→wall clock anchor on a freshly-built ring.

    The pair (one monotonic_ns and one time_ns read back-to-back)
    lets the cross-node timeline tool (trace/timeline.py) rebase
    rings from different processes onto one wall-clock axis. It lives
    HERE — in node assembly, not in trace/ — because ASY107 bans
    wall-clock reads inside the tracing plane; the anchor rides
    ``tracer.meta`` (authoritative, survives ring laps) plus a
    best-effort ``clock.anchor`` instant for raw-event consumers.
    Idempotent per tracer."""
    if not getattr(tracer, "enabled", False) or tracer.meta.get(
        "anchor_mono_ns"
    ):
        return
    mono = time.monotonic_ns()
    wall = time.time_ns()
    tracer.meta["anchor_mono_ns"] = mono
    tracer.meta["anchor_wall_ns"] = wall
    tracer.instant_at("clock.anchor", mono, tid="main", wall_ns=wall)


@dataclass
class NodeParts:
    """Everything a running node is made of (pre-networking)."""

    config: Config
    genesis: GenesisDoc
    privval: Optional[FilePV]
    app: object
    proxy: AppConns
    block_db: kv.KV
    state_db: kv.KV
    block_store: BlockStore
    state_store: StateStore
    state: State
    mempool: CListMempool
    event_bus: ev.EventBus
    block_exec: BlockExecutor
    cs: ConsensusState
    evpool: object = None
    tx_indexer: object = None
    block_indexer: object = None
    index_db: object = None
    # per-height batched indexing drain (state/indexer.py, ISSUE 15);
    # retained so Node.start can upgrade it to async + crash replay
    # and Node._shutdown can flush it bounded
    indexer_service: object = None
    # per-node tracing plane (trace/, docs/TRACE.md); NOOP when
    # [instrumentation] trace_enabled = false
    tracer: object = TRACE_NOOP
    # storage lifecycle plane (store/retention.py, ISSUE 17): always
    # constructed, a no-op until any [storage] retention/snapshot
    # knob is set; Node.start spawns its reconcile loop
    retention: object = None
    # on-disk chunked snapshots (statesync/snapshots.py); None when
    # snapshot generation is off
    snapshot_store: object = None

    def close_stores(self) -> None:
        """Release every store handle (the native logdb backend holds
        an exclusive flock; sqlite keeps fds). Idempotent."""
        for db in (self.index_db, self.block_db, self.state_db):
            if db is not None:
                try:
                    db.close()
                except Exception:
                    pass
        if hasattr(self.tx_indexer, "close"):
            try:
                self.tx_indexer.close()
            except Exception:
                pass


def build_node(
    genesis: GenesisDoc,
    privval: Optional[FilePV],
    app=None,
    config: Optional[Config] = None,
    home: Optional[str] = None,
    wal: bool = False,
) -> NodeParts:
    config = config or test_config(home or ".")
    if config.instrumentation.sanitizer:
        # runtime concurrency sanitizer (docs/LINT.md "Runtime
        # sanitizer"): MUST enable before any plane below constructs
        # its locks — wrapping is a construction-time decision, which
        # is what makes disabled mode free. Per-process, like the
        # lock-order graph it feeds.
        from ..analysis import runtime as _sanitizer

        _sanitizer.enable()
    # the native wirecodec's one-time g++ build runs on a daemon
    # thread NOW so no event loop ever pays it (ASY114 found the
    # subprocess.run reachable from reactor hot paths; module() falls
    # back to the portable codec while the build is in flight)
    from ..state import native_finalize as _native_finalize
    from ..utils import wirecodec as _wirecodec

    _wirecodec.prewarm()
    # same discipline for the native finalize lane (one GIL-releasing
    # hash/encode pass per block, state/native_finalize.py)
    _native_finalize.prewarm()
    # tracing plane: one ring per node; cross-node planes (the verify
    # path) land on the process-wide tracer, on from import; a node
    # build anchors its clock
    tracer = TRACE_NOOP
    if config.instrumentation.trace_enabled:
        tracer = Tracer(
            name=config.base.moniker or "node",
            size=config.instrumentation.trace_ring_size,
        )
        record_clock_anchor(tracer)
        record_clock_anchor(enable_global())
    if config.crypto.batch_backend:
        # operator-selected verifier backend (config.toml [crypto]
        # batch_backend); empty inherits the process-wide default so
        # embedders/tests that call set_default_backend keep control
        from ..crypto import batch as crypto_batch

        crypto_batch.set_default_backend(config.crypto.batch_backend)
    # node-side snapshot persistence (statesync/snapshots.py): built
    # whenever snapshot generation is on so a locally-constructed
    # kvstore can write straight through the disk seam (an injected
    # app is covered by the retention plane's ABCI mirror instead)
    snapshot_store = None
    if config.storage.snapshot_interval > 0:
        from ..statesync.snapshots import SnapshotStore

        snapshot_store = SnapshotStore(
            os.path.join(home, "snapshots")
            if home
            else tempfile.mkdtemp(prefix="snapshots_"),
            keep_recent=config.storage.snapshot_keep_recent,
        )
    proxy_addr = getattr(config.base, "proxy_app", "")
    if app is None and proxy_addr:
        # out-of-process app (reference proxy_app + abci transport
        # config, node/setup.go:119 createAndStartProxyAppConns)
        from ..abci.socket_client import connect_app_conns

        transport = (
            "grpc" if config.base.abci == "grpc" else "socket"
        )
        proxy = connect_app_conns(proxy_addr, transport)
        app = None
    else:
        if app is None:
            # a pruned node cannot handshake-replay from block 1 —
            # replay_blocks walks app_height+1..store_height and blocks
            # below the retention base are GONE. With the lifecycle
            # knobs on, the default app must persist its committed
            # height so a restart replays only the retained tail
            # (reference PersistentKVStoreApplication).
            s = config.storage
            lifecycle_on = bool(
                s.retain_blocks
                or s.retain_states
                or s.retain_index
                or s.snapshot_interval
            )
            app = KVStoreApplication(
                persist_path=os.path.join(home, "app_state.json")
                if home and lifecycle_on
                else None,
                snapshot_store=snapshot_store,
            )
        elif (
            snapshot_store is not None
            and getattr(app, "snapshot_store", False) is None
        ):
            # an injected kvstore-style app with the seam unset gets
            # the node's store (tests pass retain_height-knobbed apps)
            app.snapshot_store = snapshot_store
        proxy = AppConns.local(app)
    block_db = kv.open_kv(
        config.base.db_backend,
        None
        if config.base.db_backend == "memdb"
        else os.path.join(home, "blockstore.db"),
    )
    state_db = kv.open_kv(
        config.base.db_backend,
        None
        if config.base.db_backend == "memdb"
        else os.path.join(home, "state.db"),
    )
    block_store = BlockStore(block_db)
    state_store = StateStore(state_db)

    state = state_store.load()
    if state is None:
        state = genesis.make_genesis_state()
        state_store.save(state)

    # ABCI handshake: InitChain at genesis / replay stored blocks
    hs = Handshaker(state_store, state, block_store, genesis)
    state = hs.handshake(proxy)

    event_bus = ev.EventBus()
    from ..evidence.pool import EvidencePool
    from ..state.indexer import BlockIndexer, IndexerService, TxIndexer

    evpool = EvidencePool(kv.MemKV(), state_store, block_store)
    # indexing is config-gated (reference [tx_index] indexer = "kv" |
    # "null"); the service accumulates a height's events in-memory on
    # the bus and flushes ONE write_batch per height — off the commit
    # path entirely once Node.start upgrades it to the async drain
    # (state/indexer.py, ISSUE 15). "null" keeps even the
    # accumulation off the publish path.
    tx_indexer = block_indexer = index_db = indexer_service = None
    if config.tx_index.indexer == "kv":
        index_db = kv.open_kv(
            config.base.db_backend,
            None
            if config.base.db_backend == "memdb"
            else os.path.join(home, "tx_index.db"),
        )
        tx_indexer = TxIndexer(index_db)
        block_indexer = BlockIndexer(index_db)
        indexer_service = IndexerService(
            tx_indexer, block_indexer, event_bus
        )
        indexer_service.tracer = tracer
        indexer_service.start()
    elif config.tx_index.indexer == "psql":
        # write-only relational sink (reference state/indexer/sink/psql);
        # retained on the parts so Node.stop can flush + close it
        from ..state.psql_sink import PsqlSink

        sink = PsqlSink(config.tx_index.psql_conn, genesis.chain_id)
        indexer_service = IndexerService(sink, sink, event_bus)
        indexer_service.tracer = tracer
        indexer_service.start()
        tx_indexer = block_indexer = sink
    # mempool flavor by config: clist | app (fork) | nop (ADR-111)
    if config.mempool.type_ == "app":
        from ..mempool.mempool import AppMempool

        mempool = AppMempool(proxy.mempool)
    elif config.mempool.type_ == "nop":
        from ..mempool.mempool import NopMempool

        mempool = NopMempool()
    else:
        mempool = CListMempool(
            proxy.mempool,
            cache_size=config.mempool.cache_size,
            max_tx_bytes=config.mempool.max_tx_bytes,
            max_txs=config.mempool.size,
            recheck=config.mempool.recheck,
            async_recheck=config.mempool.async_recheck,
        )
    block_exec = BlockExecutor(
        state_store,
        proxy.consensus,
        mempool,
        evidence_pool=evpool,
        event_bus=event_bus,
        block_store=block_store,
        block_time_tolerance_ns=config.consensus.block_time_tolerance_ns,
    )
    wal_path = None
    if wal:
        wal_path = os.path.join(
            home or tempfile.mkdtemp(), "cs.wal"
        )
    cs = ConsensusState(
        config.consensus,
        state,
        block_exec,
        block_store,
        mempool,
        priv_validator=privval,
        event_bus=event_bus,
        wal_path=wal_path,
        evidence_pool=evpool,
    )
    cs.tracer = tracer
    mempool.tracer = tracer
    # storage lifecycle plane (store/retention.py): reconciles the
    # [storage] retention window with the app's retain_height and
    # owns ALL pruning once enabled — the executor's legacy inline
    # prune hands off through the hook (state/execution.py _prune)
    from ..store.retention import RetentionPlane

    retention = RetentionPlane(
        config.storage,
        block_store,
        state_store,
        tx_indexer=tx_indexer,
        block_indexer=block_indexer,
        evpool=evpool,
        snapshot_store=snapshot_store,
        proxy=proxy,
        wal_path=wal_path,
        home=home,
        tracer=tracer,
    )
    if retention.enabled:
        block_exec.retention_hook = retention.notify_retain_height
    return NodeParts(
        config=config,
        genesis=genesis,
        privval=privval,
        app=app,
        proxy=proxy,
        block_db=block_db,
        state_db=state_db,
        block_store=block_store,
        state_store=state_store,
        state=state,
        mempool=mempool,
        event_bus=event_bus,
        block_exec=block_exec,
        cs=cs,
        evpool=evpool,
        tx_indexer=tx_indexer,
        block_indexer=block_indexer,
        index_db=index_db,
        indexer_service=indexer_service,
        tracer=tracer,
        retention=retention,
        snapshot_store=snapshot_store,
    )


def make_genesis(
    n_validators: int,
    chain_id: str = "test-chain",
    power: int = 10,
    genesis_time_ns: int = 0,
):
    """Returns (GenesisDoc, [FilePV-like in-memory signers]).

    Genesis is backdated 1h by default so chains generated forward from
    it (1s per block) stay in the past for wall-clock checks (block-time
    tolerance, light-client drift)."""
    privs = [Ed25519PrivKey.generate() for _ in range(n_validators)]
    vals = [T.Validator(p.pub_key(), power) for p in privs]
    gen = GenesisDoc(
        chain_id=chain_id,
        validators=vals,
        genesis_time_ns=genesis_time_ns or time.time_ns() - 3_600_000_000_000,
    )
    pvs = []
    for p in privs:
        d = tempfile.mkdtemp(prefix="pv_")
        pv = FilePV(
            p, os.path.join(d, "key.json"), os.path.join(d, "state.json")
        )
        pv.save_key()
        pv.save_state()
        pvs.append(pv)
    # order pvs to match sorted validator order for convenience
    vs = gen.validator_set()
    order = {v.address: i for i, v in enumerate(vs.validators)}
    pvs.sort(key=lambda pv: order[pv.pub_key().address()])
    return gen, pvs


class LocalNet:
    """Fully-connected in-memory delivery between consensus states.

    Delivery is flood-with-dedup plus a CATCH-UP healer (the reactor's
    gossipDataForCatchup analog): a node whose round state trails a
    peer's committed height is periodically re-fed that block + commit
    through the normal commit_block path. The flood alone has no
    retransmission, so any delivery skew (batched vote windows, WAL
    group-commit broadcast deferral, loop contention) could strand a
    node in COMMIT waiting for parts nobody will ever resend — the
    real p2p reactor heals this with per-peer gossip routines, and the
    harness must match that delivery contract."""

    def __init__(
        self,
        nodes: List[NodeParts],
        drop: Optional[Callable] = None,
        heal_interval_s: float = 0.05,
    ):
        self.nodes = nodes
        self.drop = drop  # (src_idx, dst_idx, kind, payload) -> bool
        self.heal_interval_s = heal_interval_s
        self._healer: Optional[asyncio.Task] = None
        for i, n in enumerate(nodes):
            n.cs.add_broadcast_hook(self._make_hook(i))

    def _make_hook(self, src: int):
        def hook(kind, payload):
            for j, other in enumerate(self.nodes):
                if j == src:
                    continue
                if self.drop and self.drop(src, j, kind, payload):
                    continue
                try:
                    other.cs.enqueue_nowait(kind, payload, f"node{src}")
                except asyncio.QueueFull:
                    pass

        return hook

    async def start(self):
        for n in self.nodes:
            await n.cs.start()
        if self.heal_interval_s > 0 and len(self.nodes) > 1:
            self._healer = asyncio.create_task(self._heal_loop())

    async def _heal_loop(self):
        """Re-feed committed blocks to lagging nodes (reference
        consensus/reactor.go gossipDataForCatchup, harness-sized)."""
        import traceback

        from ..consensus.reactor import CommitBlockMessage

        while True:
            await asyncio.sleep(self.heal_interval_s)
            try:
                stores = [n.block_store.height() for n in self.nodes]
                for j, n in enumerate(self.nodes):
                    h = n.cs.rs.height
                    for i, m in enumerate(self.nodes):
                        if i == j or stores[i] < h:
                            continue
                        if self.drop and self.drop(
                            i, j, "commit_block", None
                        ):
                            continue
                        block = m.block_store.load_block(h)
                        commit = m.block_store.load_seen_commit(
                            h
                        ) or m.block_store.load_block_commit(h)
                        if block is None or commit is None:
                            continue
                        try:
                            n.cs.enqueue_nowait(
                                "commit_block",
                                CommitBlockMessage(
                                    block,
                                    commit,
                                    m.block_store.load_extended_commit(h),
                                ),
                                f"node{i}",
                            )
                        except asyncio.QueueFull:
                            pass
                        break
            except asyncio.CancelledError:
                raise
            except Exception:
                traceback.print_exc()

    async def stop(self):
        if self._healer is not None:
            self._healer.cancel()
            self._healer = None
        for n in self.nodes:
            # bounded (ASY110): one wedged state machine must not
            # hang the whole test net's teardown
            try:
                await asyncio.wait_for(n.cs.stop(), 15.0)
            except asyncio.TimeoutError:
                pass

    async def wait_for_height(self, height: int, timeout: float = 30.0):
        async def waiter():
            while True:
                if all(
                    n.block_store.height() >= height for n in self.nodes
                ):
                    return
                await asyncio.sleep(0.02)

        await asyncio.wait_for(waiter(), timeout)
