"""Check the yardstick itself. CPU, no chip, seconds.

    JAX_PLATFORMS=cpu python -m benchmark.selfcheck

  trace     trace_reduce.reduce over the small recorded trace beside it
            (testdata/trace_small.json.gz, cut from a v5e run of
            qa175.verify-only) against numbers worked out by hand
  opcount   opcount.int32_ops / hbm_bytes at cap 175 against a hand count
  traffic   each generator gives the same traffic for the same seed and
            another for another seed
  lookup    a throw-away configuration, mix, metric and cell are found by
            name once their files and entries exist, and no longer once
            they are gone: nothing in the harness names one
  spec      every name BENCHMARK.json uses has its file
"""

from __future__ import annotations

import copy
import gzip
import hashlib
import json
import os
import sys

from benchmark import lookup, opcount, trace_reduce


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"ok  {what}")


def check_trace() -> None:
    with gzip.open(os.path.join(lookup.HERE, "testdata", "trace_small.json.gz"), "rt") as f:
        events = json.load(f)
    with open(os.path.join(lookup.HERE, "testdata", "trace_small.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce(events)
    close = lambda a, b: abs(a - b) <= 1e-9 * max(1.0, abs(b))  # noqa: E731
    check(close(got["window_s"], want["window_s"]), "trace: the slice's length")
    check(close(got["busy_s"], want["busy_s"]), "trace: busy seconds = union of the device's intervals")
    check(
        len(got["kernel_runs_s"]) == want["kernel_runs"]
        and close(sum(got["kernel_runs_s"]), want["kernel_s"]),
        "trace: the verify program's runs and their seconds",
    )
    check(
        got["breakdown"]["idle_gaps"][0][0] == want["top_gap"]
        and close(got["breakdown"]["idle_gaps"][0][1], want["top_gap_s"]),
        "trace: the longest idle gap is named by the host's annotation",
    )
    check(got["busy_s"] <= got["window_s"], "trace: busy <= window")
    # a synthetic trace whose answer is plain: two runs of 30 ms inside a
    # 100 ms slice, the second overlapped by an op; gaps of 10/20/10 ms
    ms = 1e6
    synthetic = {
        "devices": {
            "/device:TPU:0": {
                "XLA Modules": [["jit__verify_core(1)", 10 * ms, 30 * ms], ["jit__verify_core(1)", 60 * ms, 30 * ms]],
                "XLA Ops": [["fusion.1", 10 * ms, 30 * ms], ["fusion.2", 60 * ms, 20 * ms], ["fusion.1", 70 * ms, 20 * ms]],
            }
        },
        "host": [["bench.slice", 0.0, 100 * ms], ["bench.seam", 38 * ms, 25 * ms], ["bench.verdict_wait", 0.0, 12 * ms]],
    }
    r = trace_reduce.reduce(synthetic)
    check(close(r["busy_s"], 0.060) and close(r["window_s"], 0.100), "trace: synthetic busy 60 ms of 100 ms")
    check(r["kernel_runs_s"] == [0.030, 0.030], "trace: synthetic kernel runs")
    check(r["breakdown"]["idle_gaps"][0] == ["bench.seam", 0.020], "trace: synthetic gap named bench.seam, 20 ms")
    check(r["breakdown"]["device_ops"][0] == ["fusion.1", 0.050], "trace: synthetic top op")
    # what load_events keeps of a device line: the events no other encloses,
    # named by what stands before " = " in their HLO line
    event = lambda n, s, d: type("E", (), {"name": n, "start_ns": s, "duration_ns": d})  # noqa: E731
    rows = trace_reduce.top_level([
        event("%while.1 = (s32[], s32[16384]) while(%tuple.9), body=%b", 0, 100),
        event("%fusion.2 = s32[16384] fusion(%p)", 10, 20),
        event("%fusion.3 = s32[16384] fusion(%q)", 40, 60),
        event("%copy.4 = s32[16384] copy(%r)", 100, 5),
    ])
    check(rows == [("while.1", 0.0, 100.0), ("copy.4", 100.0, 5.0)], "trace: enclosed events are left out, names are cut short")


def check_opcount() -> None:
    # by hand, cap 175: field mul 400 + 361 + 38 + 60 = 859, square
    # 210 + 190 + 171 + 38 + 60 = 669, add 20
    check((opcount.FE_MUL, opcount.FE_SQ, opcount.FE_ADD) == (859, 669, 20), "opcount: field operations")
    dbl = 3 * 859 + 4 * 669 + 6 * 20  # 5,373
    dbl_t = dbl + 859
    add_cached = 8 * 859 + 6 * 20  # 6,992
    add_mixed = 6 * 859 + 6 * 20  # 5,274
    add_full = 9 * 859 + 7 * 20  # 7,871
    to_cached = 859 + 2 * 20  # 899
    window = 3 * dbl + dbl_t + add_cached + add_mixed + 15 * 20 * 7  # 36,717
    decompress = 2 * (20 * 859 + 252 * 669)  # 371,536
    sha = 2 * (80 * 104 + 24)  # 16,688
    scalars = 512 + 800
    table = 15 * (add_full + to_cached)  # 131,550
    final = add_full + 3 * dbl + 240
    by_hand = decompress + sha + scalars + table + 64 * window + final
    check(by_hand == 2_895_204, "opcount: the hand count itself")
    check(opcount.int32_ops(175, 1) == by_hand, "opcount: int32_ops(175, 1) = 2,895,204")
    check(opcount.int32_ops(175, 1000) == 1000 * by_hand, "opcount: linear in signatures")
    check(opcount.hbm_bytes(175, 1) == 175 + 4 + 96 + 1, "opcount: hbm_bytes(175, 1) = 276")
    check(opcount.sha512_blocks(47) == 1 and opcount.sha512_blocks(175) == 2, "opcount: SHA-512 blocks by cap")


def _digest(obj) -> str:
    return hashlib.sha256(repr(obj).encode()).hexdigest()


def check_traffic() -> None:
    from benchmark.tests import tiny

    spec = tiny.spec()
    for workload in ("tiny.catchup", "tiny.verify-only"):
        cell = lookup.load_cell(spec, workload)
        gen = lookup.load_generator(cell["mix"]["generator"])
        prints = []
        for seed in (2_147_483_659, 2_147_483_659, 7):
            t = gen.Traffic(cell["config_data"], cell["mix"], seed)
            if hasattr(t, "pool"):
                prints.append(_digest([(p["block_hash"], p["sigs"]) for _, p, _ in t.pool]))
            else:
                prints.append(_digest([t.txs_by_height, t.src.block_store.load_block(t.limit).hash()]))
        check(prints[0] == prints[1], f"traffic: {cell['mix']['generator']} repeats for one seed")
        check(prints[0] != prints[2], f"traffic: {cell['mix']['generator']} differs for another seed")


def check_lookup() -> None:
    spec = copy.deepcopy(lookup.load_spec())
    made = {
        os.path.join(lookup.HERE, "configs", "throwaway.json"): json.dumps({"name": "throwaway", "validators": 4}),
        os.path.join(lookup.HERE, "traffic", "throwaway-mix.json"): json.dumps({"generator": "commit_stream", "pool_heights": 2}),
        os.path.join(lookup.HERE, "metrics", "throwaway.share.py"): "def read(record):\n    return record.get('x')\n",
    }
    spec["configs"].append({"name": "throwaway", "file": "benchmark/configs/throwaway.json"})
    spec["workloads"].append({"name": "throwaway.cell", "config": "throwaway", "traffic": "throwaway-mix", "chips": 1})
    spec["per_layer"].append({"name": "throwaway.share", "moves": "setup_s", "unit": "%", "workloads": ["throwaway.cell"]})
    try:
        for path, text in made.items():
            with open(path, "w") as f:
                f.write(text)
        cell = lookup.load_cell(spec, "throwaway.cell")
        check(cell["config_data"]["validators"] == 4 and cell["mix"]["pool_heights"] == 2, "lookup: a new configuration and mix are found by name")
        check(lookup.load_generator(cell["mix"]["generator"]).Traffic is not None, "lookup: the mix names its generator")
        names = [m["name"] for m in lookup.metrics_for(spec, "throwaway.cell", "per_layer")]
        check("throwaway.share" in names and "kernel_roofline.verify" not in names, "lookup: a cell gets the metrics that list it, and those that list none")
        check(lookup.load_reader("throwaway.share")({"x": 3.5}) == 3.5, "lookup: a new metric's reader is found by name")
        check(lookup.load_reader("throwaway.share")({}) is None, "lookup: a reader with nothing to read returns nothing")
    finally:
        for path in made:
            if os.path.exists(path):
                os.remove(path)
    try:
        lookup.load_cell(spec, "throwaway.cell")
    except FileNotFoundError:
        check(True, "lookup: gone with its files")
    else:
        raise AssertionError("the throw-away cell is still found")


def check_spec() -> None:
    spec = lookup.load_spec()
    for w in spec["workloads"]:
        cell = lookup.load_cell(spec, w["name"])
        lookup.load_generator(cell["mix"]["generator"])
    for m in spec["per_layer"]:
        lookup.load_reader(m["name"])
    e2e = {m["name"] for m in spec["end_to_end"]}
    check(all(m["moves"] in e2e for m in spec["per_layer"]), "spec: every per-layer metric moves an end-to-end metric")
    check(True, "spec: every configuration, mix, generator and reader BENCHMARK.json names has its file")


def main() -> int:
    for part in (check_opcount, check_trace, check_lookup, check_spec, check_traffic):
        part()
    print("selfcheck: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
