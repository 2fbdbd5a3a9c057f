"""scheduler (crypto/scheduler.py): signatures in device dispatches over signatures submitted, %. Moves catchup_rate."""

from benchmark.record import device_sig_share as read  # noqa: F401
