"""set-up: the first dispatch's wall minus backend-compile / cache-load seconds (jax.monitoring), s. Moves setup_s."""

from benchmark.record import trace_lower_s as read  # noqa: F401
