"""Virtual-mesh scaling beyond the driver's 8 devices (VERDICT r4 #9).

The driver dryruns n_devices=8; these tests prove the SAME full
sharded step (kernel leg + psum quorum) at 16 and 32 virtual devices,
and that the dispatch padding keeps per-device partition math exact on
a RAGGED configuration (non-power-of-two device count whose shard
width does not divide the natural pad). Kernel-compiling lane: each
mesh size is a fresh XLA program (~40-60s cold on the 1-core box,
seconds warm via .jax_cache).
"""

import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.tpu, pytest.mark.slow]  # tpu implies slow: keeps the `-m 'not slow'` fast lane kernel-free

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENTRY = os.path.join(REPO, "__graft_entry__.py")

WALL_CAP_S = 420


@pytest.mark.parametrize("n_devices", [16, 32])
def test_dryrun_at_scale(n_devices):
    """The full driver dryrun — sharded kernel leg, tally, psum
    quorum — on a 16/32-device virtual mesh. Asserts the kernel leg
    GENUINELY executed sharded (no host fallback) and the weighted
    tally is stable at every mesh size (one bad lane of 10 power)."""
    env = dict(os.environ)
    env.pop("GRAFT_DRYRUN_KERNEL", None)
    # wider meshes pay a larger partitioned-compile cost than the
    # driver's 8-device budget assumes; this test targets partition
    # math, not the driver's budget envelope (test_dryrun pins that)
    env["GRAFT_DRYRUN_KERNEL_BUDGET_S"] = "150"
    try:
        proc = subprocess.run(
            [sys.executable, ENTRY, "--dryrun", str(n_devices)],
            env=env,
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=WALL_CAP_S,
        )
    except subprocess.TimeoutExpired:
        pytest.fail(
            f"{n_devices}-device dryrun exceeded {WALL_CAP_S}s"
        )
    assert proc.returncode == 0, (
        proc.stdout[-2000:] + proc.stderr[-2000:]
    )
    assert "dryrun_multichip OK" in proc.stdout, proc.stdout[-2000:]
    line = next(
        l for l in proc.stdout.splitlines() if "kernel_leg=" in l
    )
    assert "sharded-kernel" in line, line
    assert f"mesh={n_devices}" in line, line
    # 2 lanes per device, one corrupted lane of power 10: the psum
    # tally must be exact at every mesh width
    n = n_devices * 2
    assert f"tally={10 * n - 10}/{10 * n}" in line, line


def test_ragged_lane_padding_on_6_device_mesh():
    """Non-power-of-two device count (6) with a batch whose natural
    pad (16) does not divide: dispatch must round the lanes up to a
    multiple of the device count (18), shard 3 lanes per device, and
    return exact verdicts for the real items."""
    script = f"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
sys.path.insert(0, {REPO!r})
from cometbft_tpu.utils.device import setup_compile_cache
setup_compile_cache()
import numpy as np
from cometbft_tpu.crypto import batch as cb
from cometbft_tpu.crypto import ref_ed25519 as ref
from cometbft_tpu.ops import ed25519 as ed

cb.set_default_backend("tpu")
cb.set_min_tpu_batch(1)
ed.PAD_MIN = 8  # natural pad for 9 items -> 16, NOT divisible by 6
rng = np.random.default_rng(11)
items = []
bad = {{4}}
for i in range(9):
    sk = rng.bytes(32)
    pk = ref.public_from_seed(sk)
    m = bytes(rng.bytes(21))
    sig = ref.sign(sk, m)
    if i in bad:
        sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    items.append((m, pk, sig))
got = ed.verify_batch(items)
d = ed.LAST_DISPATCH
assert d["sharded"] and d["n_devices"] == 6, d
assert d["lanes"] == 18 and d["lanes"] % 6 == 0, d
assert list(got) == [i not in bad for i in range(9)], list(got)
print("RAGGED_OK lanes=", d["lanes"])
"""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=WALL_CAP_S,
            env={
                k: v
                for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
            },
        )
    except subprocess.TimeoutExpired:
        pytest.fail(f"ragged-mesh run exceeded {WALL_CAP_S}s")
    assert proc.returncode == 0, (
        proc.stdout[-2000:] + proc.stderr[-2000:]
    )
    assert "RAGGED_OK" in proc.stdout, proc.stdout[-1000:]
