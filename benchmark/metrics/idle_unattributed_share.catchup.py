"""device: of the device's idle time in the traced slice, the share under no program span, %. Moves catchup_rate."""

from benchmark.program_spans import idle_unattributed_share as read  # noqa: F401
