"""entry (blocksync/reactor.py): blocksync.window.fetch_wait spans over the window's wall, %. Moves catchup_rate."""

from benchmark.program_spans import fetch_wait_share as read  # noqa: F401
