"""scheduler (crypto/scheduler.py): mean crypto.sched.route span (lane split and routing decision, less the dispatch), ms. Moves catchup_rate."""

from benchmark.program_spans import route_ms_per_ticket as read  # noqa: F401
