"""Component-level timing of the ed25519 verify kernel on TPU.

Times each stage separately (double chain, cached adds, table build,
select_n lookups, SHA-512, decompress, scalar ops) with the same
chained-dispatch methodology as bench.py so dispatch latency cancels.
"""

import json
import os
import sys
import time

import numpy as np

N = int(os.environ.get("PROF_N", "8192"))


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from cometbft_tpu.utils.device import setup_compile_cache

    setup_compile_cache()

    from cometbft_tpu.ops import curve25519 as curve
    from cometbft_tpu.ops import ed25519 as ed
    from cometbft_tpu.ops import fe25519 as fe
    from cometbft_tpu.ops import sc25519 as sc
    from cometbft_tpu.ops import sha512

    rng = np.random.default_rng(0)

    def rand_fe():
        return jnp.asarray(
            rng.integers(0, 1 << 13, size=(fe.NLIMBS, N), dtype=np.int32)
        )

    def rand_pt():
        # not actually on curve; arithmetic cost is identical
        return (rand_fe(), rand_fe(), rand_fe(), rand_fe())

    def timeit(name, fn, *args, inner=1):
        """Compile fn, then time CHAIN dependent dispatches."""
        comp = jax.jit(fn).lower(*args).compile()
        out = comp(*args)
        jax.block_until_ready(out)
        # measure round-trip with a tiny noop
        tiny = jax.device_put(jnp.zeros((1,), jnp.int32))
        noop = jax.jit(lambda x: x + 1).lower(tiny).compile()
        np.asarray(noop(tiny))
        rts = []
        for _ in range(3):
            t0 = time.time()
            np.asarray(noop(tiny))
            rts.append(time.time() - t0)
        rt = min(rts)
        CHAIN = 6
        best = 1e9
        for _ in range(2):
            a0 = args
            t0 = time.time()
            for _k in range(CHAIN):
                out = comp(*a0)
                if isinstance(out, tuple):
                    a0 = (out[0],) + tuple(args[1:])
                else:
                    a0 = (out,) + tuple(args[1:])
            if isinstance(out, tuple):
                np.asarray(out[0])
            else:
                np.asarray(out)
            dt = (time.time() - t0 - rt) / CHAIN
            best = min(best, dt)
        per_item = best / inner
        print(
            json.dumps(
                {
                    "stage": name,
                    "ms": round(best * 1e3, 2),
                    "ms_per_unit": round(per_item * 1e3, 3),
                    "inner": inner,
                }
            ),
            flush=True,
        )
        return best

    # --- stages -----------------------------------------------------

    q = rand_pt()

    def chain_double(x, y, z, t):
        p = tuple(fe.unstack(c) for c in (x, y, z, t))
        for _ in range(16):
            p = curve.double(p)
        return fe.stack(p[0])

    timeit("double x16", chain_double, *q, inner=16)

    cq_arr = tuple(rand_fe() for _ in range(4))

    def chain_add(x, y, z, t):
        p = tuple(fe.unstack(c) for c in (x, y, z, t))
        cq = tuple(fe.unstack(c) for c in cq_arr)
        for _ in range(16):
            p = curve.add_cached(p, cq)
        return fe.stack(p[0])

    timeit("add_cached x16", chain_add, *q, inner=16)

    def chain_mul(a, b):
        x, y = fe.unstack(a), fe.unstack(b)
        for _ in range(16):
            x = fe.mul(x, y)
        return fe.stack(x)

    timeit("fe.mul x16", chain_mul, rand_fe(), rand_fe(), inner=16)

    def chain_sqr(a, b):
        x = fe.unstack(a)
        for _ in range(16):
            x = fe.square(x)
        return fe.stack(x)

    timeit("fe.square x16", chain_sqr, rand_fe(), rand_fe(), inner=16)

    # table build: 15 adds + to_cached
    def table_build(x, y, z, t):
        A = tuple(fe.unstack(c) for c in (x, y, z, t))
        ext = curve.identity(x.shape[1:])
        outs = [curve.to_cached(ext)]
        for _ in range(15):
            ext = curve.add(ext, A)
            outs.append(curve.to_cached(ext))
        return fe.stack(outs[-1][0])

    timeit("A-table build (15 adds)", table_build, *q)

    # select_n lookup: 16-way over a (16, 20, N) per component
    tbl = jnp.asarray(
        rng.integers(0, 1 << 13, size=(16, fe.NLIMBS, N), dtype=np.int32)
    )
    ds = jnp.asarray(rng.integers(0, 16, size=(N,), dtype=np.int32))

    def chain_sel(d0):
        acc = jnp.zeros((fe.NLIMBS, N), jnp.int32)
        for k in range(16):
            sel = jnp.broadcast_to(
                ((d0 + k) % 16)[None], (fe.NLIMBS, N)
            )
            acc = acc + lax.select_n(sel, *[tbl[i] for i in range(16)])
        return acc[0] + d0

    timeit("select_n 16way x16", chain_sel, ds, inner=16)

    # SHA-512 over 175+64 = 239-byte inputs
    hin = jnp.asarray(
        rng.integers(0, 256, size=(239, N), dtype=np.uint8)
    )
    lens = jnp.full((N,), 184, jnp.int32)

    def do_sha(h):
        return sha512.sha512(h, lens, 239)

    comp = jax.jit(do_sha).lower(hin).compile()
    out = np.asarray(comp(hin))
    t0 = time.time()
    for _ in range(4):
        out = comp(hin)
    np.asarray(out)
    print(
        json.dumps(
            {"stage": "sha512 (239B)", "ms": round((time.time() - t0) / 4 * 1e3, 2)}
        ),
        flush=True,
    )

    # decompress (includes pow2523 exponentiation: ~254 squarings)
    pk = jnp.asarray(rng.integers(0, 256, size=(32, N), dtype=np.uint8))

    def do_dec(p):
        A, ok = curve.decompress(p)
        return fe.stack(A[0])

    comp = jax.jit(do_dec).lower(pk).compile()
    out = np.asarray(comp(pk))
    t0 = time.time()
    for _ in range(4):
        out = comp(pk)
    np.asarray(out)
    print(
        json.dumps(
            {"stage": "decompress x1", "ms": round((time.time() - t0) / 4 * 1e3, 2)}
        ),
        flush=True,
    )



if __name__ == "__main__":
    main()
