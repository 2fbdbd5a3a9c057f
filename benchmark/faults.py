"""The control and the planted faults that ``correct`` has to catch.

Each is a function of the traffic object, given to
``benchmark.run.execute(..., fault=...)``; it patches the program
underneath the timed path and returns an ``undo``. The benchmark's own
runs never use them: benchmark/tests drives them at a tiny size on the
CPU, and the builder drove them on the chip at the cells' own sizes
(PERF.md, "How correct is decided").

  accept_unverified   THE CONTROL. Breaks the guarantee "verdicts are
                      exact ZIP-215 per signature": the seam's batch
                      route answers every signature as valid without
                      verifying any (what a probabilistic or skipped
                      verification amounts to at its worst).
  half_batch          half of every batch left out: only the first half
                      of a seam call's commits is verified, the rest
                      taken as valid.
  verdict_altered     an answer altered where it is produced: the first
                      verdict of every scheduler ticket is flipped.
  state_unchanged     a step that returns its state unchanged: the
                      joining node's block executor applies nothing.
"""

from __future__ import annotations


def _patch(undo: list, obj, name: str, new) -> None:
    undo.append((obj, name, getattr(obj, name)))
    setattr(obj, name, new)


def _undoer(undo: list):
    def run():
        for obj, name, old in reversed(undo):
            setattr(obj, name, old)

    return run


class _AllValid:
    def __init__(self, n: int) -> None:
        self._n = n

    def result(self):
        return [True] * self._n


def accept_unverified(traffic):
    from cometbft_tpu.types import validation

    undo: list = []
    _patch(
        undo, validation, "_run_batch_async",
        lambda items, cache, priority=None, label="": _AllValid(len(items)),
    )
    return _undoer(undo)


def half_batch(traffic):
    from cometbft_tpu.blocksync import reactor
    from cometbft_tpu.types import validation

    real = validation.verify_commits_coalesced_async

    class _Padded:
        def __init__(self, handle, n: int) -> None:
            self._h, self._n = handle, n

        def result(self):
            errs = self._h.result()
            return errs + [None] * (self._n - len(errs))

    def half(chain_id, jobs, *args, **kw):
        keep = (len(jobs) + 1) // 2
        return _Padded(real(chain_id, jobs[:keep], *args, **kw), len(jobs))

    undo: list = []
    _patch(undo, validation, "verify_commits_coalesced_async", half)
    _patch(undo, reactor, "verify_commits_coalesced_async", half)
    return _undoer(undo)


def verdict_altered(traffic):
    from cometbft_tpu.crypto import scheduler as crypto_sched

    real = crypto_sched.VerifyTicket.result

    def altered(self, timeout=None):
        ok, oks = real(self, timeout)
        oks = list(oks)
        if oks:
            oks[0] = not oks[0]
        return all(oks) and bool(oks), oks

    undo: list = []
    _patch(undo, crypto_sched.VerifyTicket, "result", altered)
    return _undoer(undo)


def state_unchanged(traffic):
    def node_fault(node):
        node.block_exec.apply_verified_block = lambda state, bid, block: state

    traffic.fault = node_fault
    return lambda: None


ALL = {
    "accept_unverified": accept_unverified,
    "half_batch": half_batch,
    "verdict_altered": verdict_altered,
    "state_unchanged": state_unchanged,
}
