"""kernel dispatch (ops/ed25519.verify_batch_async): call-to-return time of the wrap, host preparation + enqueue, ms. Moves verify_rate."""

from benchmark.record import prepare_ms_per_dispatch as read  # noqa: F401
