"""verify seam (types/validation.py): mean validation.coalesce.build span of the window (entry to submit() returned), ms. Moves catchup_rate."""

from benchmark.program_spans import seam_build_ms_per_batch as read  # noqa: F401
