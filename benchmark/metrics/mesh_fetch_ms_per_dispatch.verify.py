"""device: mean ops.ed25519.fetch span (a dispatch's verdicts read back from every device that holds a shard of them; inside crypto.sched.resolve), ms. Moves verify_rate."""

from benchmark.mesh import mesh_fetch_ms_per_dispatch as read  # noqa: F401
