"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Multi-chip TPU hardware is not available in CI; shardings are validated on a
virtual CPU mesh exactly as the driver's dryrun does.  Must run before any
``import jax`` anywhere in the test process.
"""

import os

# overwrite, not setdefault: tier-1 never runs on an accelerator, even
# where one is attached (the chip is checked by chip_smoke.py)
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# XLA-compiling the full verify kernel takes tens of seconds per shape
# even in the compact CPU form, so framework tests route signature
# batches to the host verifier (identical dispatch/coalescing code,
# different backend). The kernel itself is covered by the differential
# tests in test_ed25519_verify.py (the `-m tpu` lane).
from cometbft_tpu.crypto import batch as _batch  # noqa: E402
from cometbft_tpu.utils.device import setup_compile_cache  # noqa: E402

_batch.set_default_backend("cpu")

# persistent XLA compile cache, the one rule of utils/device: where
# JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache
setup_compile_cache()
