"""entry (blocksync/reactor.py): mean jobs of blocksync.window.verify_wait: blocks a verify window under a trickle of fetched blocks, count. Moves catchup_rate."""

from benchmark.links import blocks_per_window as read  # noqa: F401
