"""scheduler (crypto/scheduler.py): mean crypto.sched.queue_wait span a ticket (submit to the dispatcher's pop), ms. Moves verify_rate."""

from benchmark.program_spans import sched_queue_wait_ms as read  # noqa: F401
