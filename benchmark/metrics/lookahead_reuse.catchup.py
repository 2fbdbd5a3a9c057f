"""entry (blocksync/reactor.py): pre-dispatched windows reused over windows verified (reactor.pipeline_stats), %. Moves catchup_rate."""

from benchmark.record import lookahead_reuse as read  # noqa: F401
