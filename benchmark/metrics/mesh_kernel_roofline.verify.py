"""ladder kernel (ops/ed25519._verify_core), lanes sharded over the host's devices: least time ALL the dispatch's devices could take for its real signatures (opcount.py, n_devices x peaks.json) over the mean per-device kernel time, %. Moves verify_rate."""

from benchmark.mesh import mesh_kernel_roofline as read  # noqa: F401
