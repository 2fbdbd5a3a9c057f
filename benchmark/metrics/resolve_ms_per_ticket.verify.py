"""scheduler (crypto/scheduler.py): mean crypto.sched.resolve span (verdict fetch, write-back, finish), ms. Moves verify_rate."""

from benchmark.program_spans import resolve_ms_per_ticket as read  # noqa: F401
