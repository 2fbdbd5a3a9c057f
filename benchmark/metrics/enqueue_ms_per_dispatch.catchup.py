"""kernel dispatch (ops/ed25519.verify_batch_async): mean ops.ed25519.enqueue span (put of the arrays and the jitted call), ms. Moves catchup_rate."""

from benchmark.program_spans import enqueue_ms_per_dispatch as read  # noqa: F401
