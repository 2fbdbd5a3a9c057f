"""open-loop generator (benchmark/generators/commit_live.py): sent minus due, 99th percentile of the window's requests, ms: how late the generator ran. Moves verify_rate."""

from benchmark.live import send_lag_ms as read  # noqa: F401
