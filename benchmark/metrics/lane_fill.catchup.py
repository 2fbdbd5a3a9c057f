"""kernel dispatch (ops/ed25519.verify_batch_async): real signatures over padded lanes, %. Moves catchup_rate."""

from benchmark.record import lane_fill as read  # noqa: F401
