"""Async coalescing vote-verification queue (crypto/coalesce.py).

The consensus-round hot path: a 150-validator vote wave must verify in
<= 2 batch dispatches, with per-vote verdicts, cache population, and
the state machine's inline re-verify hitting the cache (reference hot
path: types/vote.go:237 via consensus/state.go:2175 addVote; the
coalescing queue is the BASELINE.json north-star design).
"""

import asyncio
import time

import pytest

from cometbft_tpu import types as T
from cometbft_tpu.consensus.reactor import (
    VOTE_CHANNEL,
    ConsensusReactor,
    encode_vote_msg,
)
from cometbft_tpu.consensus.types import Step
from cometbft_tpu.crypto.coalesce import CoalescingVerifier
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.node.inprocess import build_node, make_genesis


def run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _signed(priv, chain_id, msg):
    return priv.pub_key(), msg, priv.sign(msg)


def test_one_dispatch_per_window():
    async def main():
        v = CoalescingVerifier(window_s=0.01)
        privs = [Ed25519PrivKey.generate() for _ in range(20)]
        futs = []
        for i, p in enumerate(privs):
            pk, msg, sig = _signed(p, "c", b"msg-%d" % i)
            if i == 7:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])  # corrupt one
            futs.append(v.submit(pk, msg, sig))
        oks = await asyncio.gather(*futs)
        assert v.dispatches == 1
        assert [i for i, ok in enumerate(oks) if not ok] == [7]

    run(main())


def test_cache_short_circuits_resubmit():
    async def main():
        cache = T.SignatureCache()
        v = CoalescingVerifier(cache=cache, window_s=0.005)
        p = Ed25519PrivKey.generate()
        pk, msg, sig = _signed(p, "c", b"hello")
        assert await v.submit(pk, msg, sig) is True
        assert v.dispatches == 1
        # second submit: resolved from cache, no new dispatch
        assert await v.submit(pk, msg, sig) is True
        assert v.dispatches == 1
        assert v.cache_hits == 1

    run(main())


def test_max_pending_flushes_immediately():
    async def main():
        v = CoalescingVerifier(window_s=60.0, max_pending=8)
        p = Ed25519PrivKey.generate()
        futs = [
            v.submit(*_signed(p, "c", b"m%d" % i)) for i in range(8)
        ]
        # window is 60s: only the max_pending flush can resolve these
        oks = await asyncio.wait_for(asyncio.gather(*futs), 30)
        assert all(oks)
        assert v.dispatches == 1
        await v.drain()

    run(main())


def test_150_validator_vote_wave_two_dispatches():
    """The VERDICT r1 'done' criterion: a 150-validator in-process
    round verifies its vote wave in <= 2 dispatches, bad votes are
    dropped before the state machine, and +2/3 drives the round
    forward."""

    async def main():
        gen, pvs = make_genesis(150, chain_id="wave")
        parts = build_node(gen, pvs[0])
        cs = parts.cs
        await cs.start()
        try:
            reactor = ConsensusReactor(cs, parts.block_store)
            # a block everyone pretends to prevote for
            bid = T.BlockID(b"\x11" * 32, T.PartSetHeader(1, b"\x22" * 32))
            vs = gen.validator_set()
            now = time.time_ns()

            class FakePeer:
                peer_id = "wavepeer"
                _data = {}

                def get(self, k):
                    return self._data.get(k)

                def set(self, k, v):
                    self._data[k] = v

                def try_send(self, *a, **kw):
                    return True

            peer = FakePeer()
            n_bad = 0
            for i, pv in enumerate(pvs[1:], start=1):
                vote = T.Vote(
                    type_=T.PREVOTE,
                    height=1,
                    round=0,
                    block_id=bid,
                    timestamp_ns=now,
                    validator_address=pv.pub_key().address(),
                    validator_index=i,
                    signature=b"",
                )
                sig = pv.priv_key.sign(vote.sign_bytes(gen.chain_id))
                if i == 5:  # one byzantine garbage signature
                    sig = sig[:-1] + bytes([sig[-1] ^ 1])
                    n_bad += 1
                vote.signature = sig
                reactor.receive(
                    VOTE_CHANNEL, peer, encode_vote_msg(vote)
                )
            await reactor.vote_verifier.drain()
            # let the state machine drain its queue
            for _ in range(50):
                await asyncio.sleep(0.01)
                if cs.rs.votes.prevotes(0) and (
                    cs.rs.votes.prevotes(0).sum > 0
                ):
                    if cs.queue.empty():
                        break

            ver = reactor.vote_verifier
            assert ver.submitted == 149
            assert ver.dispatches <= 2, ver.dispatches
            prevotes = cs.rs.votes.prevotes(0)
            # 148 good votes landed; the corrupted one was dropped
            # before the state machine (plus possibly our own prevote)
            good = sum(
                1
                for v in prevotes.votes
                if v is not None and v.block_id.key() == bid.key()
            )
            assert good >= 148
            assert prevotes.get_vote(5) is None
            assert prevotes.has_two_thirds_any()
            # inline add_vote re-verify hit the shared cache
            assert cs.sig_cache.hits >= 148
            # +2/3 prevotes for a block pushed the round to precommit+
            assert cs.rs.step >= Step.PRECOMMIT
        finally:
            await cs.stop()

    run(main())


@pytest.mark.parametrize("failure", ["submit", "device-dispatch"])
def test_dispatch_failure_falls_back_to_host_verification(
    monkeypatch, failure
):
    """ADVICE r2 (low): a transient backend/device error must not mark
    a whole wave invalid — the reactor already announced has_vote, so
    the dropped votes would never be re-gossiped. Per-item host
    verification resolves the lanes instead: the coalescer's own when
    the scheduler refuses the ticket, the scheduler's host chunks
    when the device dispatch fails."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import scheduler as crypto_sched
    from cometbft_tpu.ops import ed25519 as ops_ed

    sched = crypto_sched.VerifyScheduler()
    monkeypatch.setattr(crypto_sched, "scheduler", lambda: sched)
    if failure == "submit":
        sched.close()  # submit() raises: "verify scheduler closed"
    else:
        def boom(ed_items):
            raise RuntimeError("device went away")

        monkeypatch.setattr(ops_ed, "verify_batch_async", boom)
        monkeypatch.setattr(crypto_batch, "_default_backend", "tpu")
        monkeypatch.setattr(crypto_batch, "_MIN_TPU_BATCH", 1)

    async def main():
        v = CoalescingVerifier(window_s=0.005)
        privs = [Ed25519PrivKey.generate() for _ in range(6)]
        futs = []
        for i, p in enumerate(privs):
            msg = b"wave|%d" % i
            sig = p.sign(msg)
            if i == 3:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])  # one bad lane
            futs.append(v.submit(p.pub_key(), msg, sig))
        got = await asyncio.gather(*futs)
        assert got == [i != 3 for i in range(6)]

    try:
        run(main())
        if failure == "device-dispatch":
            assert sched.stats()["degraded"] == 1
    finally:
        sched.close()
