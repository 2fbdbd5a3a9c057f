"""Compile for the chip without the chip: the TPU's compiler is asked
for a described ``v5e:2x2`` (on-chip-measurement guide, section 2), so
what it would refuse costs a test run and no chip time. This is the
ONLY file that describes the chip: the process that runs it loads the
TPU library and keeps it, so the topology is described inside a
fixture, never at import, and nothing here starts a child process.

Nothing runs on a device here: a compile that passes is not a chip
run, and no time or rate is read off it.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip (the next one warns
    and compiles again): keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _ladder(topo, r):
    """Pallas ``_ladder_call`` over r sublane rows (r x 128 lanes) at
    the floor blocking of 8, on one chip."""
    from cometbft_tpu.ops import fe25519 as fe
    from cometbft_tpu.ops.pallas_ladder import _ladder_call

    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = _ladder_call.lower(
        arg(64, r, 128),
        arg(64, r, 128),
        arg(16, 4, fe.NLIMBS, r, 128),
        block=8,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _quorum(topo, lanes=32_768):
    """The psum quorum tally of parallel/sharded_verify on a 4-device
    mesh: the collective must be there."""
    from cometbft_tpu.parallel.mesh import DATA_AXIS
    from cometbft_tpu.parallel.sharded_verify import quorum_program

    assert len(topo.devices) == 4
    mesh = Mesh(np.asarray(topo.devices), (DATA_AXIS,))
    lane = NamedSharding(mesh, P(DATA_AXIS))
    compiled = quorum_program(mesh).lower(
        jax.ShapeDtypeStruct((lanes,), jnp.bool_, sharding=lane),
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=lane),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
    ).compile()
    assert "all-reduce" in compiled.as_text()
    return compiled


def _verify_sharded(topo, lanes=32_768, cap=175):
    """The whole verify program lane-sharded over four chips (8,192
    lanes each, the ``plain`` form): what ``chip_smoke.py --chips 4``
    runs. Minutes in the tuple field form, so it is compiled in the
    rolled form that run uses."""
    from cometbft_tpu.parallel.mesh import DATA_AXIS
    from cometbft_tpu.parallel.sharded_verify import make_sharded_core

    mesh = Mesh(np.asarray(topo.devices), (DATA_AXIS,))

    def arg(shape, dtype):
        spec = P(*([None] * (len(shape) - 1)), DATA_AXIS)
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    compiled = make_sharded_core(mesh, "plain").lower(
        arg((cap, lanes), jnp.uint8),
        arg((lanes,), jnp.int32),
        arg((32, lanes), jnp.uint8),
        arg((32, lanes), jnp.uint8),
        arg((32, lanes), jnp.uint8),
    ).compile()
    assert "all-reduce" not in compiled.as_text()  # no communication
    return compiled


# case -> (what to compile, the field form it is traced in). Under
# JAX_PLATFORMS=cpu the code would pick its rolled CPU form by itself,
# which Mosaic cannot lower: the test steers the form the chip gets.
CASES = {
    "pallas_ladder_r8_block8": (lambda topo: _ladder(topo, 8), "tuple"),
    "pallas_ladder_r512_block8": (lambda topo: _ladder(topo, 512), "tuple"),
    "quorum_psum_4dev": (_quorum, "tuple"),
    "verify_sharded_4dev_compact": (_verify_sharded, "compact"),
}


@pytest.mark.parametrize(
    "case",
    [
        pytest.param(c, marks=pytest.mark.slow)
        if c == "verify_sharded_4dev_compact"
        else c
        for c in CASES
    ],
)
def test_compiles_for_v5e(case, topo, no_compile_cache, monkeypatch):
    from cometbft_tpu.ops import fe25519 as fe

    build, field_form = CASES[case]
    monkeypatch.setattr(fe, "_COMPACT", field_form == "compact")
    compiled = build(topo)
    print(f"{case}: {compiled.memory_analysis()}")
