"""`python -m cometbft_tpu` — the node CLI (reference
cmd/cometbft/main.go:14-49 command registry).

Commands: init, start, testnet, light, replay, rollback,
reindex-event, reset / unsafe-reset-all, inspect, compact,
gen-node-key, gen-validator, show-node-id, show-validator, version.

Home layout (reference config directory conventions):
  <home>/config/config.toml, genesis.json, node_key.json,
               priv_validator_key.json
  <home>/data/priv_validator_state.json, *.db, cs.wal/
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import sys

VERSION = "0.1.0"


def _home(args) -> str:
    return os.path.expanduser(args.home)


def _paths(home: str) -> dict:
    return {
        "config": os.path.join(home, "config"),
        "data": os.path.join(home, "data"),
        "config_toml": os.path.join(home, "config", "config.toml"),
        "genesis": os.path.join(home, "config", "genesis.json"),
        "node_key": os.path.join(home, "config", "node_key.json"),
        "pv_key": os.path.join(home, "config", "priv_validator_key.json"),
        "pv_state": os.path.join(home, "data", "priv_validator_state.json"),
    }


def _load_config(home: str):
    from ..config.config import default_config, load_toml

    p = _paths(home)
    if os.path.exists(p["config_toml"]):
        cfg = load_toml(p["config_toml"])
    else:
        cfg = default_config(home)
    cfg.root_dir = home
    return cfg


# --- init ----------------------------------------------------------------


def cmd_init(args) -> int:
    """Initialise a home dir: config, genesis (this node as sole
    validator), node key, privval key (reference commands/init.go)."""
    from .. import types as T
    from ..config.config import default_config, write_toml
    from ..p2p.key import NodeKey
    from ..privval.file_pv import FilePV
    from ..types.genesis import GenesisDoc

    home = _home(args)
    p = _paths(home)
    os.makedirs(p["config"], exist_ok=True)
    os.makedirs(p["data"], exist_ok=True)

    cfg = default_config(home)
    if not os.path.exists(p["config_toml"]):
        write_toml(cfg, p["config_toml"])
    pv = FilePV.load_or_generate(p["pv_key"], p["pv_state"])
    nk = NodeKey.load_or_gen(p["node_key"])
    if not os.path.exists(p["genesis"]):
        gen = GenesisDoc(
            chain_id=args.chain_id
            or "test-chain-%s" % os.urandom(3).hex(),
            validators=[T.Validator(pv.pub_key(), 10)],
        )
        with open(p["genesis"], "w") as f:
            f.write(gen.to_json())
        print(f"Generated genesis file {p['genesis']}")
    print(f"Initialised node in {home} (node id {nk.node_id})")
    return 0


# --- start ---------------------------------------------------------------


def cmd_start(args) -> int:
    from ..node.node import Node
    from ..p2p.key import NodeKey
    from ..privval.file_pv import FilePV
    from ..types.genesis import GenesisDoc

    home = _home(args)
    p = _paths(home)
    cfg = _load_config(home)
    # config-selectable level, e.g. "info" or "consensus:debug,*:info"
    # (reference libs/log + config log_level)
    try:
        from ..utils.log import set_level

        set_level(cfg.base.log_level)
    except ValueError:
        print(f"invalid log_level {cfg.base.log_level!r}; using info")
    with open(p["genesis"]) as f:
        gen = GenesisDoc.from_json(f.read())
    if cfg.base.priv_validator_laddr:
        from ..privval.signer import RetrySignerClient, SignerClient

        # bounded retries around every sign call: a transient signer
        # hiccup must not become a missed vote (reference
        # privval/retry_signer_client.go)
        pv = RetrySignerClient(
            SignerClient(cfg.base.priv_validator_laddr)
        )
        print(
            f"waiting for remote signer on {pv.listen_addr} ..."
        )
        pv.wait_for_signer()
        pv.pub_key()  # prefetch + cache the validator identity
    else:
        pv = (
            FilePV.load(p["pv_key"], p["pv_state"])
            if os.path.exists(p["pv_key"])
            else None
        )
    nk = NodeKey.load_or_gen(p["node_key"])
    app = None
    if cfg.base.abci == "kvstore-appmem":
        from ..models.kvstore import AppMempoolKVStore

        app = AppMempoolKVStore()

    async def main():
        node = Node(
            cfg, gen, privval=pv, node_key=nk, app=app,
            home=os.path.join(home, "data"),
        )
        await node.start()
        print(
            f"Node {nk.node_id} started: p2p {node.listen_addr}, "
            f"rpc {node.rpc_server.listen_addr if node.rpc_server else '-'}"
        )
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        print("shutting down...")
        await node.stop()

    asyncio.run(main())
    return 0


# --- key/identity helpers ------------------------------------------------


def cmd_gen_node_key(args) -> int:
    from ..p2p.key import NodeKey

    home = _home(args)
    nk = NodeKey.load_or_gen(_paths(home)["node_key"])
    print(nk.node_id)
    return 0


def cmd_show_node_id(args) -> int:
    from ..p2p.key import NodeKey

    nk = NodeKey.load(_paths(_home(args))["node_key"])
    print(nk.node_id)
    return 0


def cmd_gen_validator(args) -> int:
    from ..privval.file_pv import FilePV

    p = _paths(_home(args))
    os.makedirs(p["config"], exist_ok=True)
    os.makedirs(p["data"], exist_ok=True)
    pv = FilePV.load_or_generate(p["pv_key"], p["pv_state"])
    print(
        json.dumps(
            {
                "address": pv.pub_key().address().hex().upper(),
                "pub_key": {
                    "type": pv.pub_key().type_,
                    "value": bytes(pv.pub_key()).hex(),
                },
            },
            indent=2,
        )
    )
    return 0


def cmd_show_validator(args) -> int:
    from ..privval.file_pv import FilePV

    p = _paths(_home(args))
    pv = FilePV.load(p["pv_key"], p["pv_state"])
    print(
        json.dumps(
            {
                "type": pv.pub_key().type_,
                "value": bytes(pv.pub_key()).hex(),
            }
        )
    )
    return 0


# --- testnet -------------------------------------------------------------


def cmd_testnet(args) -> int:
    """Generate a multi-node testnet directory tree (reference
    commands/testnet.go)."""
    from .. import types as T
    from ..config.config import default_config, write_toml
    from ..p2p.key import NodeKey
    from ..privval.file_pv import FilePV
    from ..types.genesis import GenesisDoc

    out = os.path.expanduser(args.o)
    n = args.v
    pvs, nks = [], []
    for i in range(n):
        home = os.path.join(out, f"node{i}")
        p = _paths(home)
        os.makedirs(p["config"], exist_ok=True)
        os.makedirs(p["data"], exist_ok=True)
        pvs.append(FilePV.load_or_generate(p["pv_key"], p["pv_state"]))
        nks.append(NodeKey.load_or_gen(p["node_key"]))
    gen = GenesisDoc(
        chain_id=args.chain_id or "testnet-%s" % os.urandom(3).hex(),
        validators=[T.Validator(pv.pub_key(), 10) for pv in pvs],
    )
    base_p2p = args.starting_port
    peers = ",".join(
        f"{nks[i].node_id}@127.0.0.1:{base_p2p + 2 * i}" for i in range(n)
    )
    for i in range(n):
        home = os.path.join(out, f"node{i}")
        p = _paths(home)
        cfg = default_config(home)
        cfg.p2p.laddr = f"tcp://127.0.0.1:{base_p2p + 2 * i}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{base_p2p + 2 * i + 1}"
        cfg.p2p.persistent_peers = ",".join(
            pr
            for j, pr in enumerate(peers.split(","))
            if j != i
        )
        cfg.base.moniker = f"node{i}"
        write_toml(cfg, p["config_toml"])
        with open(p["genesis"], "w") as f:
            f.write(gen.to_json())
    print(f"Wrote {n}-node testnet to {out} (chain {gen.chain_id})")
    return 0


# --- maintenance ---------------------------------------------------------


def cmd_reset(args, all_: bool = False) -> int:
    """Delete data (blocks/state/WAL) and reset privval height state
    (reference commands/reset.go). unsafe-reset-all also removes the
    address book."""
    from ..privval.file_pv import FilePV

    home = _home(args)
    p = _paths(home)
    data = p["data"]
    if os.path.isdir(data):
        for name in os.listdir(data):
            if name == "priv_validator_state.json":
                continue
            full = os.path.join(data, name)
            shutil.rmtree(full, ignore_errors=True) if os.path.isdir(
                full
            ) else os.remove(full)
    if os.path.exists(p["pv_key"]):
        pv = FilePV.load(p["pv_key"], p["pv_state"])
        pv.last = type(pv.last)()  # zero sign-state
        pv.save_state()
    print(f"Reset data in {data}")
    return 0


def cmd_rollback(args) -> int:
    from ..state.rollback import rollback_state
    from ..state.store import Store as StateStore
    from ..store.block_store import BlockStore
    from ..utils import kv

    home = _home(args)
    cfg = _load_config(home)
    data = os.path.join(home, "data")
    block_db = kv.open_kv("sqlite", os.path.join(data, "blockstore.db"))
    state_db = kv.open_kv("sqlite", os.path.join(data, "state.db"))
    st = rollback_state(
        StateStore(state_db), BlockStore(block_db), remove_block=args.hard
    )
    print(
        f"Rolled back state to height {st.last_block_height} "
        f"(app_hash {st.app_hash.hex()[:16]})"
    )
    block_db.close()
    state_db.close()
    return 0


def cmd_compact(args) -> int:
    import sqlite3

    home = _home(args)
    data = os.path.join(home, "data")
    n = 0
    for name in os.listdir(data) if os.path.isdir(data) else []:
        if name.endswith(".db"):
            con = sqlite3.connect(os.path.join(data, name))
            con.execute("VACUUM")
            con.close()
            n += 1
    print(f"Compacted {n} sqlite databases")
    return 0


def cmd_reindex_event(args) -> int:
    """Rebuild tx/block indexes from stored blocks + finalize
    responses (reference commands/reindex_event.go)."""
    from ..state.execution import decode_finalize_response
    from ..state.indexer import (
        LAST_INDEXED_KEY,
        BlockIndexer,
        TxIndexer,
        _enc_height,
    )
    from ..state.store import Store as StateStore
    from ..store.block_store import BlockStore
    from ..utils import kv

    home = _home(args)
    data = os.path.join(home, "data")
    block_db = kv.open_kv("sqlite", os.path.join(data, "blockstore.db"))
    state_db = kv.open_kv("sqlite", os.path.join(data, "state.db"))
    index_db = kv.open_kv("sqlite", os.path.join(data, "tx_index.db"))
    bs, ss = BlockStore(block_db), StateStore(state_db)
    txi, bli = TxIndexer(index_db), BlockIndexer(index_db)
    start = args.start_height or bs.base()
    end = args.end_height or bs.height()
    count = 0
    for h in range(start, end + 1):
        blk = bs.load_block(h)
        raw = ss.load_finalize_block_response(h)
        if blk is None or raw is None:
            continue
        resp = decode_finalize_response(raw)
        # ONE atomic batch per height — rows + the idx:last marker —
        # exactly the live IndexerService flush shape (ISSUE 15), so
        # a killed reindex resumes where it stopped
        sets = []
        for i, tx in enumerate(blk.data.txs):
            if i < len(resp.tx_results):
                sets.extend(txi.tx_sets(h, i, tx, resp.tx_results[i]))
        sets.extend(bli.block_sets(h, resp.events))
        # marker advances CONTIGUOUSLY only (same contract as the
        # live flush): an explicit --start-height above idx:last+1
        # must not jump the marker over never-indexed heights, or
        # IndexerService.replay() would skip them forever. A gap
        # that lies entirely below the store base is pruned —
        # unindexable — so jumping it is safe (replay's anchored
        # walk does the same).
        last = txi.last_indexed_height()
        if last >= h - 1 or bs.base() >= h:
            sets.append(
                (LAST_INDEXED_KEY, _enc_height(max(last, h)))
            )
        index_db.write_batch(sets)
        count += 1
    print(f"Reindexed {count} blocks [{start},{end}]")
    for db in (block_db, state_db, index_db):
        db.close()
    return 0


def cmd_replay(args) -> int:
    """Re-execute stored blocks against a fresh app instance via the
    handshake replay path (reference commands/replay.go)."""
    from ..node.inprocess import build_node
    from ..types.genesis import GenesisDoc

    home = _home(args)
    p = _paths(home)
    cfg = _load_config(home)
    with open(p["genesis"]) as f:
        gen = GenesisDoc.from_json(f.read())
    parts = build_node(
        gen, None, config=cfg, home=os.path.join(home, "data")
    )
    print(
        f"Replayed to height {parts.state.last_block_height} "
        f"(app_hash {parts.state.app_hash.hex()[:16]})"
    )
    return 0


def cmd_inspect(args) -> int:
    """Read-only RPC over the data dirs of a stopped node (reference
    inspect/inspect.go:32)."""
    from ..rpc.env import Environment
    from ..rpc.server import RPCServer
    from ..state.store import Store as StateStore
    from ..store.block_store import BlockStore
    from ..types import events as ev
    from ..types.genesis import GenesisDoc
    from ..utils import kv

    home = _home(args)
    p = _paths(home)
    cfg = _load_config(home)
    data = os.path.join(home, "data")
    with open(p["genesis"]) as f:
        gen = GenesisDoc.from_json(f.read())
    env = Environment(
        chain_id=gen.chain_id,
        block_store=BlockStore(
            kv.open_kv("sqlite", os.path.join(data, "blockstore.db"))
        ),
        state_store=StateStore(
            kv.open_kv("sqlite", os.path.join(data, "state.db"))
        ),
        event_bus=ev.EventBus(),
        genesis=gen,
        config=cfg,
    )

    async def main():
        srv = RPCServer(env)
        await srv.start(args.rpc_laddr)
        print(f"Inspect RPC serving on {srv.listen_addr} (read-only)")
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await srv.stop()

    asyncio.run(main())
    return 0


def cmd_light(args) -> int:
    """Light client daemon: bisection-verify new headers from a
    primary against witnesses (reference cmd light + light/proxy)."""
    from ..light import SEQUENTIAL, SKIPPING, Client, TrustOptions
    from ..light.http_provider import HTTPProvider

    primary = HTTPProvider(args.chain_id, args.primary)
    witnesses = [
        HTTPProvider(args.chain_id, w)
        for w in (args.witnesses.split(",") if args.witnesses else [])
        if w
    ]
    store = None
    if args.dir:
        # persistent trust store (reference light home db): a
        # restarted daemon resumes from its last VERIFIED header —
        # the CLI trust root only seeds an empty store
        from ..light.store import DBLightStore
        from ..utils.kv import open_kv

        os.makedirs(os.path.expanduser(args.dir), exist_ok=True)
        store = DBLightStore(
            open_kv(
                "sqlite",
                os.path.join(
                    os.path.expanduser(args.dir), "light.db"
                ),
            ),
            args.chain_id,
        )
    cli = Client(
        args.chain_id,
        TrustOptions(
            period_ns=int(args.trust_period_h * 3600 * 1e9),
            height=args.trust_height,
            hash=bytes.fromhex(args.trust_hash),
        ),
        primary=primary,
        witnesses=witnesses,
        store=store,
        verification_mode=(
            SEQUENTIAL if args.sequential else SKIPPING
        ),
    )
    if args.laddr:
        # proxy mode (the reference command's primary role): serve
        # light-verified RPC — including proof-checked abci_query/tx —
        # while tracking the head in the background
        import asyncio

        from ..light.proxy import LightProxy

        async def serve():
            proxy = LightProxy(cli, args.primary)
            addr = args.laddr
            for pfx in ("tcp://", "http://"):
                if addr.startswith(pfx):
                    addr = addr[len(pfx):]
            await proxy.start(addr)
            print(
                f"light proxy for {args.chain_id} on "
                f"{proxy.listen_addr} (primary {args.primary})"
            )
            try:
                while True:
                    try:
                        await asyncio.to_thread(cli.update)
                    except asyncio.CancelledError:
                        raise  # ctrl-C path below handles shutdown
                    except Exception as e:
                        # a transient primary hiccup must not tear the
                        # proxy daemon down; log and keep polling
                        print(f"light update failed (retrying): {e!r}")
                    await asyncio.sleep(args.interval_s)
            except (KeyboardInterrupt, asyncio.CancelledError):
                pass
            finally:
                await proxy.stop()
            return 0

        try:
            return asyncio.run(serve()) or 0
        except KeyboardInterrupt:
            return 0

    import time as _t

    print(f"light client tracking {args.chain_id} via {args.primary}")
    try:
        while True:
            lb = cli.update()
            if lb is not None:
                print(
                    f"verified height {lb.height} "
                    f"hash {lb.hash().hex()[:16]}"
                )
            _t.sleep(args.interval_s)
    except KeyboardInterrupt:
        return 0


def cmd_signer(args) -> int:
    """Run a remote signer daemon serving this home dir's validator
    key to a node (the reference ecosystem's tmkms role)."""
    from ..privval.file_pv import FilePV
    from ..privval.signer import SignerServer

    p = _paths(_home(args))
    pv = FilePV.load(p["pv_key"], p["pv_state"])
    server = SignerServer(pv, args.address)

    async def main():
        print(
            f"signer for {pv.pub_key().address().hex()[:16]} "
            f"dialing {args.address}"
        )
        while True:
            try:
                await server.serve()
            except (
                ConnectionError,
                OSError,
                EOFError,  # IncompleteReadError: node closed mid-handshake
                asyncio.TimeoutError,
            ) as e:
                print(f"connection lost ({e}); retrying in 1s")
            await asyncio.sleep(1.0)

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        pass
    return 0


def cmd_abci_server(args) -> int:
    """Host the example kvstore app out-of-process (the reference
    abci-cli's `kvstore` server command, abci/cmd/abci-cli): a node
    configured with proxy_app = this address drives it over the
    socket/grpc ABCI protocol."""
    from ..models.kvstore import KVStoreApplication

    app = KVStoreApplication(
        persist_path=os.path.join(_home(args), "data", "kvstore.json")
        if args.persist
        else None
    )
    if args.transport == "grpc":
        from ..abci.server import GRPCServer

        server = GRPCServer(app, args.address)
        server.start()
        print(f"abci grpc server on port {server.port}")
        try:
            import time as _t

            while True:
                _t.sleep(3600)
        except KeyboardInterrupt:
            server.stop()
        return 0

    from ..abci.server import ABCIServer

    server = ABCIServer(app, args.address)

    async def main():
        await server.start()
        print(f"abci socket server on {server.listen_addr}")
        await asyncio.Event().wait()

    try:
        asyncio.run(main())
    except KeyboardInterrupt:
        pass
    return 0


def cmd_abci_cli(args) -> int:
    """Client side of the reference abci-cli (abci/cmd/abci-cli):
    echo/info/check_tx/... one-shots, interactive `console`, and piped
    `batch` scripts against a running ABCI server."""
    from .abci_cli import run_abci_cli

    return run_abci_cli(args.address, args.abci_cmd, args.abci_args)


def cmd_bootstrap_state(args) -> int:
    """Offline statesync: light-verify state at a height and seed the
    stores so `start` goes straight to blocksync (reference
    node.BootstrapState, node/node.go:161-280)."""
    from ..node.bootstrap import bootstrap_state
    from ..types.genesis import GenesisDoc

    home = _home(args)
    cfg = _load_config(home)
    with open(_paths(home)["genesis"]) as f:
        gen = GenesisDoc.from_json(f.read())
    h = bootstrap_state(cfg, gen, os.path.join(home, "data"),
                        height=args.height or None)
    print(f"bootstrapped state at height {h}")
    return 0


def cmd_debug(args) -> int:
    """`debug dump` / `debug kill` (reference
    cmd/cometbft/commands/debug/): archive a live node's status,
    net_info, consensus dump, and profiles; kill additionally
    SIGKILLs the node process after the dump."""
    from ..utils.debug import collect_debug_dump

    path = collect_debug_dump(
        args.rpc_laddr.replace("tcp://", ""),
        args.output_dir,
        pprof_addr=args.pprof_laddr,
        label=args.debug_cmd,
    )
    print(f"wrote {path}")
    if args.debug_cmd == "kill":
        import signal as _sig

        if args.pid <= 0:
            print("debug kill requires --pid <node pid>", file=sys.stderr)
            return 1
        os.kill(args.pid, _sig.SIGKILL)
        print(f"killed pid {args.pid}")
    return 0


def cmd_load(args) -> int:
    """Timestamped tx load + commit-latency report (reference
    test/loadtime)."""
    import json as _json

    from ..e2e.load import LoadGenerator, latency_report
    from ..rpc.client import HTTPClient

    async def main():
        base = args.rpc_laddr.replace("tcp://", "http://")
        if not base.startswith("http"):
            base = "http://" + base
        cli = HTTPClient(base)
        try:
            st = await cli.status()
            h0 = int(st["sync_info"]["latest_block_height"])
            gen = LoadGenerator(
                cli,
                rate=args.rate,
                connections=args.connections,
                tx_size=args.size,
            )
            res = await gen.run(args.time)
            await asyncio.sleep(2.0)  # let the tail commit
            st = await cli.status()
            h1 = int(st["sync_info"]["latest_block_height"])
            rep = await latency_report(cli, h0 + 1, h1)
            print(
                _json.dumps(
                    {
                        "sent": res.sent,
                        "accepted": res.accepted,
                        "rejected": res.rejected,
                        "send_rate_tx_s": round(res.send_rate, 1),
                        **rep.to_dict(),
                    }
                )
            )
        finally:
            await cli.close()

    asyncio.run(main())
    return 0


def cmd_version(args) -> int:
    print(f"cometbft-tpu v{VERSION}")
    return 0


# --- parser --------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="cometbft-tpu",
        description="TPU-native BFT consensus engine",
    )
    ap.add_argument(
        "--home",
        default=os.environ.get("CMTHOME", "~/.cometbft-tpu"),
        help="node home directory",
    )
    sub = ap.add_subparsers(dest="command")

    p = sub.add_parser("init", help="initialise a node home dir")
    p.add_argument("--chain-id", default="")
    p.set_defaults(fn=cmd_init)

    p = sub.add_parser("start", help="run the node")
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("testnet", help="generate a local testnet")
    p.add_argument("--v", type=int, default=4, help="number of validators")
    p.add_argument("--o", default="./mytestnet", help="output directory")
    p.add_argument("--chain-id", default="")
    p.add_argument("--starting-port", type=int, default=26656)
    p.set_defaults(fn=cmd_testnet)

    for name, fn in (
        ("gen-node-key", cmd_gen_node_key),
        ("show-node-id", cmd_show_node_id),
        ("gen-validator", cmd_gen_validator),
        ("show-validator", cmd_show_validator),
        ("version", cmd_version),
        ("compact", cmd_compact),
        ("replay", cmd_replay),
    ):
        p = sub.add_parser(name)
        p.set_defaults(fn=fn)

    p = sub.add_parser("reset", help="delete data, keep keys")
    p.set_defaults(fn=cmd_reset)
    p = sub.add_parser("unsafe-reset-all", help="delete data, keep keys")
    p.set_defaults(fn=lambda a: cmd_reset(a, all_=True))

    p = sub.add_parser("rollback", help="rewind state by one height")
    p.add_argument(
        "--hard", action="store_true", help="also delete the tip block"
    )
    p.set_defaults(fn=cmd_rollback)

    p = sub.add_parser("reindex-event", help="rebuild tx/block indexes")
    p.add_argument("--start-height", type=int, default=0)
    p.add_argument("--end-height", type=int, default=0)
    p.set_defaults(fn=cmd_reindex_event)

    p = sub.add_parser("inspect", help="read-only RPC over data dirs")
    p.add_argument("--rpc-laddr", default="127.0.0.1:26657")
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser("signer", help="remote signer daemon")
    p.add_argument(
        "-a", "--address", required=True,
        help="validator node's priv_validator_laddr to dial",
    )
    p.set_defaults(fn=cmd_signer)

    p = sub.add_parser(
        "bootstrap-state",
        help="seed stores with light-verified state (offline statesync)",
    )
    p.add_argument("--height", type=int, default=0)
    p.set_defaults(fn=cmd_bootstrap_state)

    p = sub.add_parser("debug", help="dump/kill a live node")
    p.add_argument("debug_cmd", choices=("dump", "kill"))
    p.add_argument("--pid", type=int, default=0, help="pid (kill only)")
    p.add_argument("--rpc-laddr", default="127.0.0.1:26657")
    p.add_argument("--pprof-laddr", default="")
    p.add_argument("--output-dir", default=".")
    p.set_defaults(fn=cmd_debug)

    p = sub.add_parser(
        "load", help="generate tx load and report commit latency"
    )
    p.add_argument("--rpc-laddr", default="127.0.0.1:26657")
    p.add_argument("-r", "--rate", type=float, default=100.0)
    p.add_argument("-c", "--connections", type=int, default=1)
    p.add_argument("-s", "--size", type=int, default=256)
    p.add_argument("-T", "--time", type=float, default=10.0)
    p.set_defaults(fn=cmd_load)

    p = sub.add_parser(
        "abci-server", help="host the kvstore app over socket/grpc ABCI"
    )
    p.add_argument("-a", "--address", default="tcp://127.0.0.1:26658")
    p.add_argument(
        "-t", "--transport", choices=("socket", "grpc"), default="socket"
    )
    p.add_argument(
        "--persist", action="store_true", help="persist app state to home"
    )
    p.set_defaults(fn=cmd_abci_server)

    p = sub.add_parser(
        "abci-cli",
        help="client for a running ABCI app: one-shot, console, batch",
    )
    p.add_argument("-a", "--address", default="tcp://127.0.0.1:26658")
    p.add_argument(
        "abci_cmd",
        choices=(
            "echo", "info", "check_tx", "finalize_block",
            "prepare_proposal", "process_proposal", "commit", "query",
            "console", "batch",
        ),
    )
    p.add_argument("abci_args", nargs="*")
    p.set_defaults(fn=cmd_abci_cli)

    p = sub.add_parser("light", help="light client daemon / proxy")
    p.add_argument("chain_id")
    p.add_argument("-p", "--primary", required=True)
    p.add_argument("-w", "--witnesses", default="")
    p.add_argument("--trust-height", type=int, required=True)
    p.add_argument("--trust-hash", required=True)
    p.add_argument("--trust-period-h", type=float, default=168.0)
    p.add_argument("--interval-s", type=float, default=1.0)
    p.add_argument(
        "--sequential",
        action="store_true",
        help="verify every header in order instead of 9/16 skipping "
        "bisection (reference cmd light --sequential)",
    )
    p.add_argument(
        "--dir",
        default="",
        help="persist the trust store here (light.db); a restart "
        "resumes from the last verified header instead of the CLI "
        "trust root (reference light home dir)",
    )
    p.add_argument(
        "--laddr",
        default="",
        help="serve the light-verified RPC proxy on this address "
        "(headers/commits/validators/blocks verified; abci_query and "
        "tx responses proof-checked against the verified AppHash — "
        "reference `cometbft light` serves :8888)",
    )
    p.set_defaults(fn=cmd_light)

    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if not getattr(args, "fn", None):
        build_parser().print_help()
        return 1
    try:
        return args.fn(args)
    except KeyboardInterrupt:
        return 130
    except FileNotFoundError as e:
        print(
            f"Error: {e.filename or e} not found — "
            "did you run `init` in this home dir?",
            file=sys.stderr,
        )
        return 1
    except Exception as e:
        if os.environ.get("CMT_DEBUG"):
            raise
        print(f"Error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
