"""host apply (state/, store/): blocksync.window.apply + .persist spans over blocks applied, ms. Moves catchup_rate."""

from benchmark.record import apply_ms_per_block as read  # noqa: F401
