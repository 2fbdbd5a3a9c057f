"""``correct`` comes out true on a sound run and false under the
control and under every planted fault a cell can have.

The harness is driven from ``execute`` on (the look for a chip is
skipped) at 8 validators on the host verifier; the installed probes sit
above the faults, as in a real run.
"""

from __future__ import annotations

import pytest

from benchmark import faults, run
from benchmark.tests import tiny

SEED = 2_147_483_659  # past 32 signed bits, as the driver's can be
CELLS = ("tiny.catchup", "tiny.verify-only")


def drive(workload: str, fault=None, seconds: float = 2.5) -> dict:
    undo = []

    def hook(traffic):
        if fault is not None:
            undo.append(faults.ALL[fault](traffic))

    try:
        return run.execute(tiny.spec(), workload, SEED, seconds, False, tiny.DEVICE, fault=hook)
    finally:
        for u in undo:
            u()


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    r = drive(workload)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["accept_unverified", "half_batch", "verdict_altered"])
def test_control_and_faults_are_not_correct(workload, fault):
    # a join that a flipped verdict stalls ends with the window
    r = drive(workload, fault)
    assert r["correct"] is False, r["compared"]
    failing = [k for k, v in r["compared"].items() if v["value"] > v["limit"]]
    assert failing


def test_state_unchanged_is_not_correct():
    r = drive("tiny.catchup", "state_unchanged")
    assert r["correct"] is False, r["compared"]
