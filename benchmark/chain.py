"""Signed source chains from a seed.

The benchmark's own copy of utils/chaingen.make_chain, with what that
one lacks: a tx-size parameter, txs and block times drawn from the
seed, and the list of every block's txs handed back for the plain
reference. Blocks are built through the program's BlockExecutor, one
after another and with no verification, so the chain in the source
store is the serial reference of what a joining node must arrive at.
"""

from __future__ import annotations

import numpy as np

_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)


def make_txs(rng, height: int, n: int, tx_bytes: int) -> list:
    """``n`` distinct "key=value" txs for one block; ``tx_bytes`` 0
    gives the short form of utils/chaingen, else each tx is exactly
    that long, the value filled with hex digits from the seed."""
    if not tx_bytes:
        return [b"h%d_%d=v%d" % (height, i, height) for i in range(n)]
    heads = [b"h%d_%d=" % (height, i) for i in range(n)]
    fill = _HEX[rng.integers(0, 16, size=(n, tx_bytes), dtype=np.uint8)]
    return [
        h + fill[i, : tx_bytes - len(h)].tobytes()
        for i, h in enumerate(heads)
    ]


def validator_seeds(seed: int, n: int) -> list:
    rng = np.random.default_rng([seed, 1])
    return [rng.bytes(32) for _ in range(n)]


def genesis_time_ns(seed: int) -> int:
    return (1_700_000_000 + seed % 1_000_000) * 1_000_000_000


def build_source(config: dict, seed: int):
    """(genesis, source NodeParts, txs by height): the deployment's
    chain in a memdb store."""
    import cometbft_tpu.types as T
    from cometbft_tpu.config.config import test_config
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.node.inprocess import build_node
    from cometbft_tpu.types.genesis import GenesisDoc

    privs = [
        Ed25519PrivKey.from_seed(s)
        for s in validator_seeds(seed, config["validators"])
    ]
    gen = GenesisDoc(
        chain_id=config["chain_id"],
        validators=[
            T.Validator(p.pub_key(), config["voting_power"]) for p in privs
        ],
        genesis_time_ns=genesis_time_ns(seed),
    )
    cfg = test_config(".")
    cfg.base.db_backend = "memdb"
    src = build_node(gen, None, config=cfg)
    rng = np.random.default_rng([seed, 2])
    txs_by_height = _extend(
        src, privs, config["chain_blocks"], config["txs_per_block"],
        config.get("tx_bytes", 0), rng,
    )
    return gen, src, txs_by_height


def _extend(node, privs, n_blocks, txs_per_block, tx_bytes, rng) -> list:
    import cometbft_tpu.types as T

    state = node.state_store.load()
    chain_id = state.chain_id
    t = state.last_block_time_ns
    addr_to_priv = {p.pub_key().address(): p for p in privs}
    txs_by_height = []
    for h in range(1, n_blocks + 1):
        proposer = state.validators.get_proposer()
        last_commit = node.block_store.load_seen_commit(h - 1) if h > 1 else None
        txs = make_txs(rng, h, txs_per_block, tx_bytes)
        for tx in txs:
            node.mempool.check_tx(tx)
        t += 1_000_000_000
        block, parts = node.block_exec.create_proposal_block(
            h, state, last_commit, proposer.address, time_ns=t
        )
        if list(block.data.txs) != txs:
            raise RuntimeError(
                f"block {h} holds {len(block.data.txs)} of {len(txs)} txs"
            )
        txs_by_height.append(txs)
        bid = T.BlockID(block.hash(), parts.header)
        sigs = []
        for i, val in enumerate(state.validators.validators):
            vote = T.Vote(
                type_=T.PRECOMMIT, height=h, round=0, block_id=bid,
                timestamp_ns=t, validator_address=val.address,
                validator_index=i,
            )
            sigs.append(
                T.CommitSig(
                    block_id_flag=T.BLOCK_ID_FLAG_COMMIT,
                    validator_address=val.address,
                    timestamp_ns=t,
                    signature=addr_to_priv[val.address].sign(
                        vote.sign_bytes(chain_id)
                    ),
                )
            )
        commit = T.Commit(height=h, round=0, block_id=bid, signatures=sigs)
        node.block_store.save_block(block, parts, commit)
        state = node.block_exec.apply_verified_block(state, bid, block)
    node.state = state
    return txs_by_height
