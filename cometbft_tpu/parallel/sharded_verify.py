"""Multi-chip commit verification: shard_map over signature lanes.

Two composable sharded programs:

  1. ``make_sharded_core`` — each device runs the ed25519 precomp
     verify kernel on its shard of the signature lanes (ops/ed25519,
     pure VPU work, no communication). This is what the production
     ``verify_batch`` seam dispatches on multi-device hosts.
  2. ``make_quorum_reducer`` — weighted voting-power tally of the
     verdict lanes, reduced with a single ``psum`` over ICI, plus the
     quorum compare.

Together they mirror the reference's VerifyCommit semantics
(types/validation.go:30: sum voting power of valid signatures, compare
against 2/3 of total) — but the signature work is spread over chips
instead of one Go routine's batch, and the kernel graph compiles once
independently of the (cheap) communication step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..ops import ed25519 as ed
from .mesh import DATA_AXIS


def _core_specs(mode):
    """(per-device kernel, PartitionSpecs of its arguments) of a
    kernel form: every argument is split along its lane axis."""
    spec_lanes = P(None, DATA_AXIS)     # (bytes, N)
    spec_limbs = P(None, None, DATA_AXIS)  # (4, 20, N)
    spec_vec = P(DATA_AXIS)             # (N,)
    if mode == "precomp":
        return ed._verify_core_precomp, (
            spec_lanes,  # msgs
            spec_vec,    # lens
            spec_limbs,  # precomputed A
            spec_lanes,  # pks
            spec_lanes,  # rs
            spec_lanes,  # ss
        )
    if mode == "precomp_tuple":
        # pytree A: 4 components x NLIMBS separate (N,) leaves, each
        # lane-sharded — the spec mirrors the pytree structure
        from ..ops import fe25519 as fe

        a_specs = tuple(
            tuple(spec_vec for _ in range(fe.NLIMBS))
            for _ in range(4)
        )
        return ed._verify_core_precomp_tuple, (
            spec_lanes,  # msgs
            spec_vec,    # lens
            a_specs,     # A as tuple-of-limbs pytree
            spec_lanes,  # pks
            spec_lanes,  # rs
            spec_lanes,  # ss
        )
    return ed._verify_core, (
        spec_lanes,  # msgs
        spec_vec,    # lens
        spec_lanes,  # pks
        spec_lanes,  # rs
        spec_lanes,  # ss
    )


def core_shardings(mesh, mode="precomp"):
    """The NamedShardings of ``make_sharded_core``'s arguments, as a
    pytree of the arguments' own structure: what
    ``jax.device_put(host_arrays, ...)`` takes to send each device its
    own lanes (ops/ed25519's ``ops.ed25519.put`` stage)."""
    return _on_mesh(mesh, _core_specs(mode)[1])


def make_sharded_core(mesh, mode="precomp"):
    """Lane-sharded verify kernel: per-device ZIP-215 verdicts, no
    cross-device communication (the tally/quorum reduction lives in
    ``make_quorum_reducer``; the host path in types/validation.py does
    its own arbitrary-precision tally). ``mode`` selects the kernel:
    "precomp" (host-expanded A, small per-device widths), "plain"
    (bulk widths), or "precomp_tuple" (pytree A — docs/PERF.md lever
    #6) — same width rule as single-device dispatch
    (ops/ed25519.PRECOMP_MAX_LANES).

    This is the PRODUCTION seam: ``ops/ed25519.verify_batch_async``
    (the verify scheduler's device dispatch, crypto/scheduler.py)
    routes through this whenever more than one local device is
    visible, so every VerifyCommit* caller scales over the mesh
    transparently.
    """
    inner, in_specs = _core_specs(mode)
    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    return jax.jit(fn, in_shardings=_on_mesh(mesh, in_specs))


def _on_mesh(mesh, specs):
    """NamedShardings for a pytree of PartitionSpecs. Given as the
    jit's ``in_shardings`` they make a HOST array go to the devices
    shard by shard; without them a host array lands whole on the
    first device and is resharded from there."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def quorum_program(mesh):
    """The jitted sharded tally itself: weighted sum of the verdict
    lanes per device, one ``psum`` over ICI, the quorum compare.
    (ok, powers, threshold) -> (quorum, tally, ok)."""
    spec_vec = P(DATA_AXIS)

    def local(ok, powers, threshold):
        local_tally = jnp.sum(
            jnp.where(ok, powers, 0), dtype=jnp.int32
        )
        tally = jax.lax.psum(local_tally, DATA_AXIS)  # rides ICI
        return tally > threshold, tally, ok

    in_specs = (spec_vec, spec_vec, P())
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P(), spec_vec),
            check_vma=False,
        ),
        in_shardings=_on_mesh(mesh, in_specs),
    )


def make_quorum_reducer(mesh):
    """Tiny sharded step: weighted tally of verdict lanes + one psum
    over ICI + quorum compare. Composes with make_sharded_core so the
    expensive kernel graph compiles ONCE; the communication pattern
    (the part a multi-chip dryrun must prove) compiles in seconds.

    The on-device tally is int32: the returned wrapper enforces total
    voting power < 2^31 host-side before dispatch. The production path
    (types/validation.py) recomputes the authoritative tally host-side
    in arbitrary precision either way; this fast-path verdict exists
    for callers that want the quorum decision without a host round
    trip per job (reference VerifyCommit semantics,
    types/validation.go:30).
    """
    jitted = quorum_program(mesh)

    def step(ok, powers, threshold):
        total = int(np.asarray(powers, dtype=np.int64).sum())
        if total >= 2**31:
            raise ValueError(
                "total voting power overflows the int32 device tally; "
                "use the host tally path (types/validation.py)"
            )
        return jitted(ok, powers, threshold)

    return step
