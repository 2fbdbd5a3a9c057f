"""entry (blocksync/reactor.py): blocksync.window.verify_wait spans over the window's wall, %. Moves catchup_rate."""

from benchmark.record import verify_wait_share as read  # noqa: F401
