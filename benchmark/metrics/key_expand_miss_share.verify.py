"""kernel dispatch (ops/ed25519.verify_batch_async): of the distinct keys the window's precomp dispatches saw, the share _expand_pubkey did not find in _A_CACHE, %. Moves verify_rate."""

from benchmark.live import key_expand_miss_share as read  # noqa: F401
