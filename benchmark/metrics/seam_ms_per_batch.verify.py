"""verify seam (types/validation.py): the benchmark's clock around verify_commits_coalesced_async, ms a call. Moves verify_rate."""

from benchmark.record import seam_ms_per_batch as read  # noqa: F401
