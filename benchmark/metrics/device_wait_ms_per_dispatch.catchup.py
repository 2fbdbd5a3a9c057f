"""device: mean crypto.sched.device_wait span (kernel + launch + readiness notice + any wait behind the dispatch before), ms. Moves catchup_rate."""

from benchmark.program_spans import device_wait_ms_per_dispatch as read  # noqa: F401
