"""kernel dispatch (ops/ed25519.verify_batch_async): of the window's dispatches, the share whose ops.ed25519.pack span says ladder=pallas, %. Moves verify_rate."""

from benchmark.ladder import pallas_dispatch_share as read  # noqa: F401
