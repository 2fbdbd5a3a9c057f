"""verify seam (types/validation.py): mean validation.coalesce.fold span of the window (verdicts in hand to errors returned), ms. Moves verify_rate."""

from benchmark.program_spans import seam_fold_ms_per_batch as read  # noqa: F401
