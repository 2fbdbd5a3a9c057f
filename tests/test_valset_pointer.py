"""ValidatorsInfo pointer scheme (reference state/store.go:185-251,
590-640): full valset records only at change/checkpoint heights,
pointer records elsewhere, priority reconstruction on load, and the
slim S:state blob carrying EXACT live priorities (VERDICT r2 next-round
#4 — the replay pipeline's dominant cost was four full valset encodings
per height)."""

import dataclasses

import pytest

from cometbft_tpu import types as T
from cometbft_tpu.state import store as state_store_mod
from cometbft_tpu.state.state_types import ConsensusParams, State
from cometbft_tpu.state.store import Store, VALSET_CHECKPOINT_INTERVAL
from cometbft_tpu.utils import kv


def _mk_state(vs, h, initial=1, changed=1):
    nvals = vs.copy_increment_proposer_priority(1)
    return State(
        chain_id="ptr-chain",
        initial_height=initial,
        last_block_height=h,
        last_block_id=T.BlockID(b"\x01" * 32, T.PartSetHeader(1, b"\x02" * 32)),
        last_block_time_ns=1000 + h,
        validators=vs,
        next_validators=nvals,
        last_validators=vs.copy(),
        last_height_validators_changed=changed,
        consensus_params=ConsensusParams(),
        app_hash=b"\x0b" * 32,
    )


def _evolve(store, vs0, n_heights, change_at=()):
    """Simulate the executor's per-height save loop from genesis."""
    state = _mk_state(vs0.copy(), 0, changed=1)
    store.save(state)  # genesis save (next_height == initial)
    for h in range(1, n_heights + 1):
        nvals = state.next_validators.copy()
        changed = state.last_height_validators_changed
        if h in change_at:
            extra = T.random_validator_set(1)[0].validators[0]
            nvals.update_with_change_set([extra])
            changed = h + 2  # updates from block h take effect at h+2
        nvals.increment_proposer_priority(1)
        state = dataclasses.replace(
            state,
            last_block_height=h,
            validators=state.next_validators.copy(),
            next_validators=nvals,
            last_validators=state.validators.copy(),
            last_height_validators_changed=changed,
        )
        store.save(state)
    return state


def test_pointer_records_written_for_unchanged_heights():
    vs, _ = T.random_validator_set(4)
    db = kv.MemKV()
    store = Store(db)
    _evolve(store, vs, 20)
    full = pointer = 0
    for h in range(1, 23):
        raw = db.get(b"S:vi:" + h.to_bytes(8, "big"))
        assert raw is not None, h
        got, changed = state_store_mod._decode_validators_info(raw)
        if got is None:
            pointer += 1
            assert changed == 1
        else:
            full += 1
    # genesis-adjacent records are full; the rest are pointers
    assert full <= 3 and pointer >= 19


def test_load_reconstructs_priorities_at_pointer_heights():
    vs, _ = T.random_validator_set(5)
    db = kv.MemKV()
    store = Store(db)
    state = _evolve(store, vs, 30)
    # membership + hash identical at every height
    for h in (2, 7, 19, 31):
        got = store.load_validators(h)
        assert got is not None
        assert got.hash() == vs.hash()
    # the live state's priorities round-trip EXACTLY through the slim
    # blob (no reconstruction drift on the consensus-resume path)
    loaded = store.load()
    for a, b in (
        (loaded.validators, state.validators),
        (loaded.next_validators, state.next_validators),
        (loaded.last_validators, state.last_validators),
    ):
        assert [v.proposer_priority for v in a.validators] == [
            v.proposer_priority for v in b.validators
        ]
        assert a.proposer.address == b.proposer.address
    assert loaded.last_block_height == state.last_block_height


def test_valset_change_writes_full_record():
    vs, _ = T.random_validator_set(4)
    db = kv.MemKV()
    store = Store(db)
    _evolve(store, vs, 12, change_at={6})
    raw = db.get(b"S:vi:" + (8).to_bytes(8, "big"))
    got, changed = state_store_mod._decode_validators_info(raw)
    assert got is not None and changed == 8
    assert got.size() == 5
    # heights after the change reconstruct from the new full record
    after = store.load_validators(11)
    assert after.size() == 5
    # heights before it still load the old membership
    before = store.load_validators(6)
    assert before.size() == 4


def test_checkpoint_bounds_reconstruction(monkeypatch):
    monkeypatch.setattr(
        state_store_mod, "VALSET_CHECKPOINT_INTERVAL", 10
    )
    vs, _ = T.random_validator_set(3)
    db = kv.MemKV()
    store = Store(db)
    _evolve(store, vs, 25)
    # checkpoint heights hold full records
    for cp in (10, 20):
        raw = db.get(b"S:vi:" + cp.to_bytes(8, "big"))
        got, _ = state_store_mod._decode_validators_info(raw)
        assert got is not None, cp
    # a height just past a checkpoint reconstructs from it, not genesis
    assert store.load_validators(21).hash() == vs.hash()


def test_prune_keeps_reconstruction_anchor(monkeypatch):
    monkeypatch.setattr(
        state_store_mod, "VALSET_CHECKPOINT_INTERVAL", 10
    )
    vs, _ = T.random_validator_set(3)
    db = kv.MemKV()
    store = Store(db)
    _evolve(store, vs, 25)
    store.prune_states(15)
    # the checkpoint at 10 (anchor for pointer records in [10, 20)) kept
    raw = db.get(b"S:vi:" + (10).to_bytes(8, "big"))
    assert raw is not None
    # heights >= retain still load
    assert store.load_validators(15).hash() == vs.hash()
    assert store.load_validators(22).hash() == vs.hash()
    # heights below the anchor are gone
    assert db.get(b"S:vi:" + (5).to_bytes(8, "big")) is None


def test_prune_keeps_legacy_anchor_of_upgraded_store(monkeypatch):
    """ADVICE r3 (medium): on a store upgraded from the legacy S:vals
    layout, prune_states with retain_height inside the legacy region
    must not delete the legacy record that post-upgrade pointer
    records anchor at (save() anchors them at the state's
    last_height_validators_changed, which can predate retain_height —
    and an upgrade-backfill FULL record in between must not mask the
    pointer's true anchor)."""
    from cometbft_tpu.utils import codec

    monkeypatch.setattr(state_store_mod, "VALSET_CHECKPOINT_INTERVAL", 10)
    vs, _ = T.random_validator_set(3)
    db = kv.MemKV()
    # legacy store: raw S:vals full records at heights 1..12
    for h in range(1, 13):
        db.set(
            b"S:vals:" + h.to_bytes(8, "big"),
            codec.encode_validator_set(vs),
        )
    store = Store(db)
    # first post-upgrade save: last change happened at legacy height 11
    state = _mk_state(vs.copy(), 12, changed=11)
    store.save(state)
    # the new record at 14 is a pointer anchored at 11 (max(cp=10, 11));
    # save() backfills a FULL record at 13 (no legacy record there)
    raw14 = db.get(b"S:vi:" + (14).to_bytes(8, "big"))
    got14, changed14 = state_store_mod._decode_validators_info(raw14)
    assert got14 is None and changed14 == 11
    store.prune_states(12)
    # the anchor at 11 survives even though 11 < retain_height
    assert db.get(b"S:vals:" + (11).to_bytes(8, "big")) is not None
    got = store.load_validators(14)
    assert got is not None and got.hash() == vs.hash()
    # retain_height ON the backfill FULL record at 13: a full record is
    # not a change point, so the pointer at 14 still anchors below it —
    # the scan must look past full records, not stop at them
    store.prune_states(13)
    assert db.get(b"S:vals:" + (11).to_bytes(8, "big")) is not None
    got = store.load_validators(14)
    assert got is not None and got.hash() == vs.hash()


def test_legacy_full_records_still_load():
    """Stores written before the pointer scheme (raw S:vals records)
    keep loading."""
    from cometbft_tpu.utils import codec

    vs, _ = T.random_validator_set(4)
    db = kv.MemKV()
    db.set(
        b"S:vals:" + (9).to_bytes(8, "big"), codec.encode_validator_set(vs)
    )
    store = Store(db)
    got = store.load_validators(9)
    assert got is not None and got.hash() == vs.hash()


def test_rollback_across_valset_change_keeps_history_consistent():
    """Code-review r3 finding: rollback after a validator-set change
    must clamp last_height_validators_changed (reference
    rollback.go:69-76) or the next save writes a FORWARD pointer over
    a correct record and historical loads return the wrong set."""
    from cometbft_tpu.node.inprocess import build_node, make_genesis
    from cometbft_tpu.state.rollback import rollback_state
    from cometbft_tpu.utils.chaingen import make_chain

    gen, pvs = make_genesis(4, chain_id="rb-ptr")
    node = build_node(gen, None)
    make_chain(gen, [pv.priv_key for pv in pvs], 5, node=node)
    # a validator-power update lands in block 6 -> takes effect at 8
    new_power_tx = b"val:%s!%d" % (
        pvs[0].priv_key.pub_key().key_bytes.hex().encode(),
        25,
    )
    node.mempool.check_tx(new_power_tx)
    make_chain(gen, [pv.priv_key for pv in pvs], 1, node=node, txs_per_block=0)
    st = node.state_store.load()
    assert st.last_height_validators_changed == 8
    make_chain(gen, [pv.priv_key for pv in pvs], 2, node=node)
    before = node.state_store.load_validators(7)
    assert before is not None

    # roll back height 8 (the change-effect height)
    rolled = rollback_state(node.state_store, node.block_store)
    assert rolled.last_block_height == 7
    assert rolled.last_height_validators_changed <= 9
    # saving the rolled-back state must NOT have corrupted height 7/8
    after = node.state_store.load_validators(7)
    assert after is not None
    assert after.hash() == before.hash()
    # and the reloaded state still reconstructs
    reloaded = node.state_store.load()
    assert reloaded.last_block_height == 7
    assert reloaded.validators.hash() == rolled.validators.hash()


def test_pool_soft_exclusion_steers_retry():
    """EC-miss refetch prefers a different peer (soft exclusion), but
    ignores the exclusion when no alternative exists (liveness)."""
    from cometbft_tpu.blocksync.pool import BlockPool, PoolPeer

    pool = BlockPool(1)
    # direct peer construction: set_peer_range spawns requester tasks,
    # which needs a running loop this sync test doesn't have
    # 'fast' has delivered at ten times the rate 'slow' has
    pool.peers["fast"] = PoolPeer(
        "fast", object(), base=1, height=100, bytes=5_000_000, busy_s=1.0
    )
    pool.peers["slow"] = PoolPeer(
        "slow", object(), base=1, height=100, bytes=500_000, busy_s=1.0
    )
    # un-excluded: fastest wins
    assert pool._pick_peer(5).peer_id == "fast"
    pool.exclude_peer_for_height(5, "fast")
    assert pool._pick_peer(5).peer_id == "slow"
    # other heights unaffected
    assert pool._pick_peer(6).peer_id == "fast"
    # all excluded -> exclusion ignored (never a liveness risk)
    pool.exclude_peer_for_height(5, "slow")
    assert pool._pick_peer(5) is not None
    pool.clear_exclusions(5)
    assert pool._pick_peer(5).peer_id == "fast"
