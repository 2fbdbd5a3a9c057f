"""entry (blocksync/pool.py): blocks the pool took from the peers under the receive-rate floor over blocks applied, %. Moves catchup_rate."""

from benchmark.links import slow_peer_block_share as read  # noqa: F401
