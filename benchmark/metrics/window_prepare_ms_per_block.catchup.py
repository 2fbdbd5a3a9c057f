"""entry (blocksync/reactor.py): blocksync.window.prepare spans (they enclose the window's validation.coalesce.build) over blocks applied, ms. Moves catchup_rate."""

from benchmark.program_spans import window_prepare_ms_per_block as read  # noqa: F401
