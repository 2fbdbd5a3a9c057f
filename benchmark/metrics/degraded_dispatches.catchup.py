"""scheduler (crypto/scheduler.py): stats()['degraded'] over the window, count. Moves catchup_rate."""

from benchmark.record import degraded_dispatches as read  # noqa: F401
