"""entry (blocksync/pool.py): a join's start to the rate ban of its slow peer (blocksync.pool.ban), mean over the joins that banned it, s. Moves catchup_rate."""

from benchmark.links import slow_peer_ban_s as read  # noqa: F401
