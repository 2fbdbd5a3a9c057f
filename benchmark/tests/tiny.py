"""The tiny cells the benchmark's own tests drive on the CPU: the
harness from ``execute`` on, the look for a chip skipped."""

from __future__ import annotations

import copy

from benchmark import lookup

DEVICE = {"platform": "cpu", "kind": "cpu (test)", "count": 1}


def spec() -> dict:
    s = copy.deepcopy(lookup.load_spec())
    s["configs"] = [
        {"name": "tiny-kvstore", "source": "none", "reduced": [],
         "file": "benchmark/testdata/tiny-kvstore.json", "why": "tests"}
    ]
    s["workloads"] = [
        {"name": "tiny.catchup", "config": "tiny-kvstore", "traffic": "join-loop",
         "chips": 1, "why": "tests"},
        {"name": "tiny.verify-only", "config": "tiny-kvstore",
         "traffic": "tiny-commit-stream", "chips": 1, "why": "tests"},
    ]
    for m in s["end_to_end"] + s["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [
                "tiny.catchup" if "catchup" in w else "tiny.verify-only"
                for w in m["workloads"]
            ]
    return s
