"""kernel dispatch (ops/ed25519.verify_batch_async): mean ops.ed25519.pack span (shape choice and per-item fill), ms. Moves catchup_rate."""

from benchmark.program_spans import pack_ms_per_dispatch as read  # noqa: F401
