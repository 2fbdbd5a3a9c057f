"""device: 1 - union of device-busy intervals over the traced slice, %. Moves verify_rate."""

from benchmark.record import device_idle_share as read  # noqa: F401
