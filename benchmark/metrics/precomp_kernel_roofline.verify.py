"""ladder kernel (ops/ed25519._verify_core_precomp): least time for a dispatch's real signatures by the precomp form's count (benchmark/live.py, peaks.json) over kernel time, %. Moves verify_rate."""

from benchmark.live import precomp_kernel_roofline as read  # noqa: F401
