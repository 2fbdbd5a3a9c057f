"""verify seam (types/validation.py): mean validation.commit.fold span a commit (verdicts in hand to return or raise: cache feed + tally), ms. Moves verify_rate."""

from benchmark.live import commit_fold_ms as read  # noqa: F401
