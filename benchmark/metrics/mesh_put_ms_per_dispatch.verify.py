"""kernel dispatch (ops/ed25519.verify_batch_async): mean ops.ed25519.put span (a dispatch's host arrays placed on its devices by the program's shardings; inside ops.ed25519.enqueue), ms. Moves verify_rate."""

from benchmark.mesh import mesh_put_ms_per_dispatch as read  # noqa: F401
