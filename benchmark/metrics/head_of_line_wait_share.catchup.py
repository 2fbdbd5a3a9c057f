"""entry (blocksync/pool.py): blocksync.window.fetch_wait spans that began with blocks buffered (the wait is for the head alone) over the window's wall, %. Moves catchup_rate."""

from benchmark.links import head_of_line_wait_share as read  # noqa: F401
