"""verify seam (types/validation.py): mean validation.commit.build span a commit (verify_commit's entry to submit() returned, on the caller's thread), ms. Moves verify_rate."""

from benchmark.live import commit_build_ms as read  # noqa: F401
