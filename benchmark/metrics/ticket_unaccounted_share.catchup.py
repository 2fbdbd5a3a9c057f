"""scheduler (crypto/scheduler.py): per ticket, the share of build's start to resolve's end that no stage span covers, mean over the window, %. Moves catchup_rate."""

from benchmark.program_spans import ticket_unaccounted_share as read  # noqa: F401
