"""ladder kernel (ops/pallas_ladder._ladder_kernel): least time for the ladder's work on a dispatch's real signatures (benchmark/ladder.py's count, peaks.json) over its device time, %. Moves verify_rate."""

from benchmark.ladder import ladder_roofline as read  # noqa: F401
