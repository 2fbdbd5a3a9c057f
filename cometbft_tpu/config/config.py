"""Node configuration (reference config/config.go, 12 sections + TOML).

Dataclass-backed with TOML round-trip (tomllib read; simple writer).
Includes the fork-added sections: BlockSync.adaptive_sync
(config.go:1194) and the crypto backend selection for the TPU verifier.
"""

from __future__ import annotations

import os
from dataclasses import asdict, dataclass, field
from typing import List, Optional

try:
    import tomllib
except ImportError:  # pragma: no cover - py<3.11: same-API backport
    try:
        import tomli as tomllib
    except ImportError:
        tomllib = None


@dataclass
class BaseConfig:
    chain_id: str = ""
    moniker: str = "tpu-node"
    db_backend: str = "sqlite"
    db_dir: str = "data"
    log_level: str = "info"
    genesis_file: str = "config/genesis.json"
    priv_validator_key_file: str = "config/priv_validator_key.json"
    priv_validator_state_file: str = "data/priv_validator_state.json"
    node_key_file: str = "config/node_key.json"
    # when set, keys live with a REMOTE signer that dials in here
    # (reference PrivValidatorListenAddr)
    priv_validator_laddr: str = ""
    abci: str = "kvstore"
    # out-of-process app: address of an abci.server.ABCIServer /
    # GRPCServer (reference proxy_app, config/config.go Base); when set
    # (and abci is "socket" or "grpc") the node dials instead of
    # building an in-process app
    proxy_app: str = ""
    filter_peers: bool = False


@dataclass
class RPCConfig:
    laddr: str = "tcp://127.0.0.1:26657"
    # legacy gRPC broadcast API (Ping/BroadcastTx) beside JSON-RPC
    # (reference GRPCListenAddress, rpc/grpc/api.go); "" = disabled
    grpc_laddr: str = ""
    max_open_connections: int = 900
    max_subscription_clients: int = 100
    timeout_broadcast_tx_commit_s: float = 10.0
    # expose the unsafe route set (reference --rpc.unsafe: dial_seeds,
    # dial_peers, unsafe_flush_mempool); never enable on public nodes
    unsafe: bool = False


@dataclass
class P2PConfig:
    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    seeds: str = ""
    persistent_peers: str = ""
    max_num_inbound_peers: int = 40
    max_num_outbound_peers: int = 10
    flush_throttle_ms: int = 10
    max_packet_msg_payload_size: int = 1024
    send_rate: int = 5_120_000
    recv_rate: int = 5_120_000
    pex: bool = True
    seed_mode: bool = False
    handshake_timeout_s: float = 20.0
    dial_timeout_s: float = 3.0
    use_libp2p_equivalent: bool = False  # fork: lp2p transport selection
    use_autopool: bool = False  # fork: autopool reactor msg draining
    # --- self-healing connectivity plane (p2p/reconnect.py) -----------
    # full-jitter backoff for the per-peer fast reconnect lane
    reconnect_base_s: float = 1.0
    reconnect_cap_s: float = 30.0
    # fast-lane dial BUDGET per outage (not a give-up bound: spending
    # it parks the peer in the never-give-up slow lane)
    reconnect_fast_attempts: int = 12
    # slow-lane sweep period: steady-state redial load for peers whose
    # fast budget is spent
    reconnect_slow_interval_s: float = 30.0
    # zero peers for this long = starving (PEX re-learn storm on every
    # dial success; cometbft_p2p_starvation_seconds accumulates)
    starvation_s: float = 10.0
    # RPC health `connectivity` verdict: degraded below this many
    # peers (once the node has evidence it is meant to be connected)
    min_peers: int = 1


@dataclass
class MempoolConfig:
    type_: str = "clist"  # clist | nop | app (fork)
    recheck: bool = True
    broadcast: bool = True
    size: int = 5000
    cache_size: int = 10000
    max_tx_bytes: int = 1024 * 1024
    max_txs_bytes: int = 64 * 1024 * 1024
    # ingest plane (docs/PERF.md "Mempool ingest plane"): micro-batch
    # coalescing in front of CheckTx — max txs per batch, and how long
    # the drainer waits after the first tx before flushing a partial
    # batch (latency bound for a lone RPC submission)
    batch_max_txs: int = 256
    batch_flush_ms: float = 2.0
    # post-commit recheck off the consensus critical section:
    # update() snapshots and returns; verdicts apply in the
    # background, height-guarded, with unrechecked txs masked from
    # reap. Off = the reference's synchronous recheck-inside-update.
    async_recheck: bool = True


@dataclass
class StateSyncConfig:
    enable: bool = False
    rpc_servers: List[str] = field(default_factory=list)
    trust_height: int = 0
    trust_hash: str = ""
    trust_period_s: float = 168 * 3600.0
    discovery_time_s: float = 15.0
    chunk_request_timeout_s: float = 10.0


@dataclass
class BlockSyncConfig:
    enable: bool = True
    adaptive_sync: bool = False  # fork feature (config.go:1194)


@dataclass
class ConsensusConfig:
    wal_file: str = "data/cs.wal/wal"
    timeout_propose_s: float = 3.0
    timeout_propose_delta_s: float = 0.5
    timeout_prevote_s: float = 1.0
    timeout_prevote_delta_s: float = 0.5
    timeout_precommit_s: float = 1.0
    timeout_precommit_delta_s: float = 0.5
    timeout_commit_s: float = 1.0
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval_s: float = 0.0
    peer_gossip_sleep_s: float = 0.1
    peer_query_maj23_sleep_s: float = 2.0
    # max allowed difference between proposed block time and wall clock
    # (reference config/config.go:1265-1286, default 60s; 0 disables)
    block_time_tolerance_ns: int = 60_000_000_000
    # --- live-consensus fast path (docs/PERF.md) ---------------------
    # WAL group commit: sync-barrier records written within this
    # window coalesce into ONE fsync (consensus/wal.py write_group);
    # externalization (own vote/proposal broadcast) is deferred until
    # the covering fsync lands, so the WAL-before-act contract holds
    # with a bounded (~window) barrier. Routing is calibrated: the
    # seam only engages when the measured fsync cost exceeds the
    # ticket-handoff cost (slow sync-through disks), so a cached-NVMe
    # box keeps the strict inline barrier automatically. 0 disables
    # the seam entirely (the reference's one-fsync-per-barrier path).
    wal_group_commit_ms: float = 2.0
    # in-round vote-verify micro-batching: peer votes for the current
    # height arriving within this window are signature-verified as one
    # batch through the crypto coalesce/parallel engine and resolve as
    # cache hits in add_vote (the blocksync pre-verify pattern applied
    # to live rounds). 0 (default) = serial inline verification — the
    # batch only wins once committee vote waves are large enough to
    # out-earn the dispatch handoff (docs/PERF.md); the p2p reactor's
    # always-on coalescing continues to serve networked nodes either
    # way.
    vote_batch_window_ms: float = 0.0
    # pipelined finalize: block persist + WAL end-height + ABCI apply
    # run off-loop (one in-flight height, barrier before the next
    # commit) while the loop keeps relaying gossip; next-height
    # messages park and replay at height entry. Off = the reference's
    # blocking finalize.
    finalize_pipeline: bool = False
    # native finalize lane riding the pipeline (docs/PERF.md): the
    # hash/encode/persist leg of the ABCI apply (one GIL-releasing
    # native pass per block, state/native_finalize.py) takes a second
    # to_thread hop so the loop keeps relaying gossip through it.
    # Only engages when finalize_pipeline is on; off = the apply runs
    # whole on-loop exactly like the serial path.
    finalize_offload_apply: bool = True

    def propose_timeout(self, round_: int) -> float:
        return self.timeout_propose_s + self.timeout_propose_delta_s * round_

    def prevote_timeout(self, round_: int) -> float:
        return self.timeout_prevote_s + self.timeout_prevote_delta_s * round_

    def precommit_timeout(self, round_: int) -> float:
        return (
            self.timeout_precommit_s + self.timeout_precommit_delta_s * round_
        )


@dataclass
class StorageConfig:
    discard_abci_responses: bool = False
    # --- retention plane (store/retention.py, docs/STORAGE.md) -----
    # blocks/states/index rows kept behind the committed head; 0 =
    # retain everything (reference semantics — pruning entirely off).
    # The effective prune target is min-reconciled with the app's
    # retain_height from ABCI Commit; node-side windows only ever
    # TIGHTEN what the app allows, never override it upward.
    retain_blocks: int = 0
    retain_states: int = 0
    retain_index: int = 0
    # background reconcile cadence + per-batch height budget: each
    # batch is ONE atomic write_batch (deletes + base-marker advance)
    # so a crash mid-prune resumes idempotently
    prune_interval_s: float = 10.0
    prune_batch: int = 100
    # node-side snapshot generation (statesync/snapshots.py): take an
    # on-disk chunked app snapshot every `snapshot_interval` heights
    # (0 = off), rotating to the newest `snapshot_keep_recent`
    snapshot_interval: int = 0
    snapshot_keep_recent: int = 2


@dataclass
class TxIndexConfig:
    indexer: str = "kv"  # kv | null | psql
    # connection string for the psql sink (reference [tx-index]
    # psql-conn); required when indexer = "psql"
    psql_conn: str = ""


@dataclass
class InstrumentationConfig:
    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    # profiling listener (reference pprof_laddr, node/node.go:624):
    # serves /debug/pprof/{stacks,profile,heap} when set
    pprof_laddr: str = ""
    # stuck-await watchdog (the deadlock-detection analog, reference
    # libs/sync/deadlock.go): tasks suspended at the same await point
    # longer than this are reported with their stack; 0 disables
    watchdog_stall_s: float = 0.0
    # always-on tracing plane (cometbft_tpu/trace, docs/TRACE.md):
    # per-node fixed-memory event ring; the disabled fast path is a
    # single attribute check, the enabled cost is ~2us per span
    trace_enabled: bool = True
    # events retained per node (ring slots, preallocated; oldest
    # events are overwritten once the ring laps)
    trace_ring_size: int = 16384
    # cross-node causal tracing (docs/TRACE.md "Cross-node
    # timelines"): consensus/mempool/blocksync p2p messages carry a
    # compact trace-context stamp (origin, height/round/kind, send
    # instant) so receivers record correlated recv instants and the
    # `trace timeline` CLI can stitch all rings into one view.
    # Decoding and receive-side arrival recording are always on
    # (while the tracer is enabled); this only gates the OUTBOUND
    # stamp — and is moot while trace_enabled is false.
    trace_msg_stamp: bool = True
    # runtime health plane (cometbft_tpu/obs, docs/OBS.md): the
    # event-loop watchdog measures scheduling lag via a monotonic
    # heartbeat and fires the loop-stall flight recorder (thread +
    # task stack snapshot into the trace ring) when a callback blocks
    # the loop past the stall threshold. Always-on by default — the
    # heartbeat is one task wakeup per interval.
    loop_watchdog: bool = True
    # heartbeat period (the lag-sample rate; also bounds how quickly a
    # stall is noticed: detection latency ~ interval + stall threshold)
    loop_lag_interval_ms: float = 100.0
    # loop blocked longer than this => flight record (0 < stall)
    loop_stall_ms: float = 500.0
    # bounded shutdown (obs/shutdown.py, docs/OBS.md): per-stage
    # budget for Node._shutdown — a stage (reactor stops, peer
    # drain, consensus halt, store release) that overruns is
    # flight-recorded into the trace ring, cancelled, and if it
    # ignores the cancel, abandoned so the remaining stages (store
    # fd release above all) still run. Turns the stop-path wedge
    # class into a diagnosed bounded failure.
    shutdown_stage_budget_s: float = 5.0
    # runtime concurrency sanitizer (analysis/runtime.py, docs/LINT.md
    # "Runtime sanitizer"): lock-order graph with deadlock-potential
    # cycle detection, loop-affinity guard on hot-plane objects, and
    # stall attribution for the watchdog's flight records. The
    # enablement is PER-PROCESS and construction-time (hot-plane
    # locks are wrapped as planes are built), matching the per-
    # process lock-order graph. Default OFF for production nodes —
    # disabled mode costs nothing (raw locks come back unchanged);
    # config.test_config and the chaos net switch it ON, so the whole
    # tier-1 suite + 50-scenario matrix run sanitized.
    sanitizer: bool = False


@dataclass
class CryptoConfig:
    """TPU-native addition: the signature-verification backend knob.

    batch_backend is one of crypto/batch.BACKENDS, the name the one
    routing decision (crypto/batch.decide, asked by the verify
    scheduler crypto/scheduler.py once a ticket) goes by: "tpu"
    (device lanes where the measured crossover says the device wins,
    host chunks on the parallel plane otherwise), "cpu" (host chunks
    inline on the dispatcher thread: the serial baseline),
    "cpu-parallel" (host chunks on the multi-core plane,
    crypto/parallel_verify), "mesh" (multi-chip: every eligible
    dispatch's lanes shard over every local device through the
    shard_map program of parallel/sharded_verify, no calibration
    gate; DEGRADABLE: with fewer than two devices it verifies on the
    cpu-parallel host plane, so a no-mesh box may select it). Empty
    (the default) inherits the process-wide default
    (crypto/batch.set_default_backend, "tpu" unless the embedder
    changed it); a non-empty value is applied at node build
    (node/inprocess.build_node). The parallel plane's knobs are env:
    GRAFT_VERIFY_WORKERS / _TIER / _CHUNK_TARGET_MS / _MIN_PARALLEL."""

    batch_backend: str = ""  # "" (inherit) | tpu | cpu | cpu-parallel | mesh


@dataclass
class FleetConfig:
    """TPU-native addition: serving-fleet knobs (cometbft_tpu/fleet,
    docs/FLEET.md). The SessionRouter in front of N follower replicas
    admits at most max_sessions concurrent routed sessions, holds
    consistency-token barrier waits to token_wait_s, degrades a
    replica stalled past max_lag_heights behind the committee head
    (checked every lag_poll_s), and on failover replays at most
    resume_replay_max heights from the store per resumed session
    (beyond that the session is shed honestly rather than resumed
    with a gap)."""

    max_sessions: int = 4096
    admit_timeout_s: float = 0.25
    max_lag_heights: int = 8
    lag_poll_s: float = 0.1
    token_wait_s: float = 2.0
    resume_replay_max: int = 512
    drain_timeout_s: float = 5.0


# single source of truth for the fault-injection knobs ([fuzz] TOML
# section, reference config/config.go:896)
from ..p2p.fuzz import FuzzConnConfig  # noqa: E402


@dataclass
class Config:
    base: BaseConfig = field(default_factory=BaseConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    statesync: StateSyncConfig = field(default_factory=StateSyncConfig)
    blocksync: BlockSyncConfig = field(default_factory=BlockSyncConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    storage: StorageConfig = field(default_factory=StorageConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)
    instrumentation: InstrumentationConfig = field(
        default_factory=InstrumentationConfig
    )
    fuzz: FuzzConnConfig = field(default_factory=FuzzConnConfig)
    crypto: CryptoConfig = field(default_factory=CryptoConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    root_dir: str = "."

    def path(self, rel: str) -> str:
        return os.path.join(self.root_dir, rel)


def default_config(root_dir: str = ".") -> Config:
    c = Config()
    c.root_dir = root_dir
    return c


def test_config(root_dir: str = ".") -> Config:
    """Short timeouts for in-process tests (reference config.TestConfig)."""
    c = default_config(root_dir)
    c.consensus.timeout_propose_s = 0.4
    c.consensus.timeout_propose_delta_s = 0.1
    c.consensus.timeout_prevote_s = 0.2
    c.consensus.timeout_prevote_delta_s = 0.1
    c.consensus.timeout_precommit_s = 0.2
    c.consensus.timeout_precommit_delta_s = 0.1
    c.consensus.timeout_commit_s = 0.1
    c.consensus.peer_gossip_sleep_s = 0.01
    c.base.db_backend = "memdb"
    c.rpc.laddr = "tcp://127.0.0.1:0"  # ephemeral port per test node
    c.p2p.laddr = "tcp://127.0.0.1:0"
    # tests run with the runtime concurrency sanitizer ON (the
    # "race detector in CI" default; docs/LINT.md)
    c.instrumentation.sanitizer = True
    return c


def load_toml(path: str) -> Config:
    assert tomllib is not None
    with open(path, "rb") as f:
        raw = tomllib.load(f)
    c = default_config(os.path.dirname(os.path.dirname(path)) or ".")
    for section, cls_name in (
        ("base", "base"),
        ("rpc", "rpc"),
        ("p2p", "p2p"),
        ("mempool", "mempool"),
        ("statesync", "statesync"),
        ("blocksync", "blocksync"),
        ("consensus", "consensus"),
        ("storage", "storage"),
        ("tx_index", "tx_index"),
        ("instrumentation", "instrumentation"),
        ("fuzz", "fuzz"),
        ("crypto", "crypto"),
        ("fleet", "fleet"),
    ):
        if section in raw:
            obj = getattr(c, cls_name)
            for k, v in raw[section].items():
                if hasattr(obj, k):
                    setattr(obj, k, v)
    return c


def write_toml(cfg: Config, path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)

    def emit(name, obj):
        lines = [f"[{name}]"]
        for k, v in asdict(obj).items():
            if v is None:
                continue  # TOML has no null; absent key loads as default
            if isinstance(v, bool):
                lines.append(f"{k} = {'true' if v else 'false'}")
            elif isinstance(v, (int, float)):
                lines.append(f"{k} = {v}")
            elif isinstance(v, list):
                inner = ", ".join(f'"{x}"' for x in v)
                lines.append(f"{k} = [{inner}]")
            else:
                lines.append(f'{k} = "{v}"')
        return "\n".join(lines)

    sections = [
        ("base", cfg.base),
        ("rpc", cfg.rpc),
        ("p2p", cfg.p2p),
        ("mempool", cfg.mempool),
        ("statesync", cfg.statesync),
        ("blocksync", cfg.blocksync),
        ("consensus", cfg.consensus),
        ("storage", cfg.storage),
        ("tx_index", cfg.tx_index),
        ("instrumentation", cfg.instrumentation),
        ("fuzz", cfg.fuzz),
        ("crypto", cfg.crypto),
        ("fleet", cfg.fleet),
    ]
    with open(path, "w") as f:
        f.write("\n\n".join(emit(n, o) for n, o in sections) + "\n")
