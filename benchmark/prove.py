"""Read ``correct``'s numbers on the chip, many seeds in one warmed process.

    python -m benchmark.prove --workloads <a>:<seconds>,<b>:<seconds> \
        --seeds <first> --count 12 --control-seeds 3

Set-up is long (the verify program is traced again in every process),
so the sound runs that give each number's lower reading, the control's
runs that give its upper one, and one run of each planted fault a cell
can have (its mix lists them under ``faults``) share one process: every
run is ``run.execute`` whole, at the cell's own configuration and mix,
with a short window. The benchmark's own runs never come here. Each
run's compared numbers are printed, and appended to ``--out`` as one
JSON line.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from benchmark import faults, lookup, run
from benchmark.probes import find_tpu, say

CONTROL = "accept_unverified"


def one(spec, workload, seed, seconds, device, fault, out=""):
    undo = []

    def hook(traffic):
        undo.append(faults.ALL[fault](traffic))

    try:
        r = run.execute(
            spec, workload, seed, seconds, False, device,
            fault=hook if fault else None,
        )
    finally:
        for u in undo:
            u()
        gc.unfreeze()  # what execute froze for its window: this run's traffic
    row = {
        "workload": workload,
        "seed": seed,
        "fault": fault,
        "correct": r["correct"],
        "attempted": r["attempted"],
        "failed": r["failed"],
        "compared": {k: v["value"] for k, v in r["compared"].items()},
        "metrics": {k: v["value"] for k, v in r["metrics"].items() if k != "setup_s"},
    }
    say(f"prove: {json.dumps(row)}")
    if out:  # row by row: a run that dies keeps those before it
        with open(out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", type=int, required=True, help="the first seed")
    ap.add_argument("--count", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    spec = lookup.load_spec()
    rows = []
    bad = 0
    for item in args.workloads.split(","):
        workload, _, seconds = item.partition(":")
        seconds = float(seconds or 10.0)
        cell = lookup.load_cell(spec, workload)
        device = find_tpu(cell["chips"])
        if device is None:
            return 2
        seeds = [args.seeds + 7919 * k for k in range(args.count)]
        for seed in seeds:
            rows.append(one(spec, workload, seed, seconds, device, None, args.out))
            bad += not rows[-1]["correct"]
        for seed in seeds[: args.control_seeds]:
            rows.append(one(spec, workload, seed, seconds, device, CONTROL, args.out))
            bad += rows[-1]["correct"]
        for fault in cell["mix"]["faults"]:
            rows.append(one(spec, workload, seeds[0], seconds, device, fault, args.out))
            bad += rows[-1]["correct"]
    say(f"prove: {len(rows)} runs, {bad} came out the wrong way")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
