"""device: per dispatch, the last device's end of its verify program run minus the first device's, from the trace's XLA Modules rows, mean over the slice, ms. Moves verify_rate."""

from benchmark.mesh import mesh_shard_skew as read  # noqa: F401
