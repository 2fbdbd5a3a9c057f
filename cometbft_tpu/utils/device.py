"""The one seam onto the JAX backend: what this process runs on, and
where its compile cache lives.

Every place that asks "is this the CPU platform?", "how many local
devices?" or "which chip?" asks here. The answer is memoised (a
backend cannot change once it has started) and a backend that cannot
start RAISES: an accelerator that fails to initialise is an error to
be seen, never a quiet "then it is the CPU" that routes every batch to
the host for the life of the process.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class Backend(NamedTuple):
    platform: str  # "tpu", "cpu", ...
    kind: str      # jax's device_kind, e.g. "TPU v5 lite"
    count: int     # devices visible to this process


@functools.lru_cache(maxsize=1)
def backend() -> Backend:
    """Platform, device kind and device count as JAX reports them.
    Initialises the backend on first call and raises what JAX raises
    when it cannot (a failure is not cached: the next call tries
    again and raises again)."""
    import jax

    devs = jax.devices()
    return Backend(devs[0].platform, devs[0].device_kind, len(devs))


def on_cpu() -> bool:
    """Is the process's JAX backend the CPU platform (tests, the
    virtual-device dryrun) rather than an accelerator?"""
    return backend().platform == "cpu"


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and no directory is set in code; otherwise the cache is
    ``<checkout>/.jax_cache``. Never a temporary, per-process or timed
    path: what one process compiled, the next must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
