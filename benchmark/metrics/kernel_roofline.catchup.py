"""ladder kernel (ops/ed25519._verify_core): least time for the dispatch's real signatures (opcount.py, peaks.json) over kernel time, %. Moves catchup_rate."""

from benchmark.record import kernel_roofline as read  # noqa: F401
