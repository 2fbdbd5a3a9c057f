"""Profile the blocksync replay HOST pipeline (VERDICT r3 #7).

Runs a bounded replay over the cached bench corpus with the host
verify backend under cProfile and prints the per-stage breakdown, so
the next replay lever is chosen from data (docs/PERF.md records the
findings). Usage:

    python profile_replay.py [n_blocks=1500] [window=128]
"""

import asyncio
import cProfile
import io
import os
import pstats
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main():
    n_blocks = int(sys.argv[1]) if len(sys.argv) > 1 else 1500
    window = int(sys.argv[2]) if len(sys.argv) > 2 else 128

    import bench
    from cometbft_tpu.blocksync import BlockSyncReactor
    from cometbft_tpu.config.config import test_config
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.node.inprocess import build_node
    from cometbft_tpu.utils.chaingen import StorePeerClient

    crypto_batch.set_default_backend("cpu")
    gen, privs, parts = bench._corpus(
        int(os.environ.get("BENCH_REPLAY_BLOCKS", "10000"))
    )

    cfg = test_config(".")
    cfg.base.db_backend = "memdb"
    fresh = build_node(gen, None, config=cfg)

    async def run():
        caught = asyncio.Event()
        reactor = BlockSyncReactor(
            fresh.state,
            fresh.block_exec,
            fresh.block_store,
            on_caught_up=lambda st: caught.set(),
            verify_window=window,
        )
        reactor.pool.set_peer_range(
            "src", StorePeerClient(parts), 1, n_blocks
        )
        await reactor.start()
        await asyncio.wait_for(caught.wait(), 3600)
        await reactor.stop()
        return reactor.blocks_applied

    prof = cProfile.Profile()
    t0 = time.time()
    prof.enable()
    applied = asyncio.run(run())
    prof.disable()
    wall = time.time() - t0
    print(
        f"replayed {applied} blocks in {wall:.1f}s "
        f"({applied / wall:.1f} blocks/s, host backend, "
        f"window={window})\n"
    )
    for sort, title, n in (
        ("cumulative", "BY CUMULATIVE TIME", 35),
        ("tottime", "BY SELF TIME", 35),
    ):
        out = io.StringIO()
        st = pstats.Stats(prof, stream=out)
        st.sort_stats(sort).print_stats(n)
        print(f"===== {title} =====")
        body = out.getvalue()
        # keep header + rows, drop the noise preamble
        print("\n".join(body.splitlines()[4:]))


if __name__ == "__main__":
    main()
