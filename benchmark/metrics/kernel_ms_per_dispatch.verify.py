"""ladder kernel (ops/ed25519._verify_core): device time of one run of the verify program in the profiler's trace, ms. Moves verify_rate."""

from benchmark.record import kernel_ms_per_dispatch as read  # noqa: F401
