"""entry (blocksync/pool.py): bytes the pool counted from the peers at or above the floor over what their links could carry between each join's first request and last block, %. Moves catchup_rate."""

from benchmark.links import link_utilisation as read  # noqa: F401
