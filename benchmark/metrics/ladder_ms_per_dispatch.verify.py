"""ladder kernel (ops/pallas_ladder._ladder_kernel): mean device time, a run of the verify program, of the ops named after the kernel's jitted call (benchmark/ladder.LADDER_OP), ms. Moves verify_rate."""

from benchmark.ladder import ladder_ms_per_dispatch as read  # noqa: F401
