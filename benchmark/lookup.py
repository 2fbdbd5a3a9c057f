"""Everything the harness knows of a cell it finds by name.

BENCHMARK.json names a workload's configuration and traffic mix. The
configuration is ``benchmark/configs/<name>.json`` (the ``file`` of its
entry), the mix ``benchmark/traffic/<name>.json``, whose ``generator``
names ``benchmark/generators/<generator>.py``, and each per-layer metric
``benchmark/metrics/<name>.py`` with one function ``read(record)``.
Adding a cell, a configuration, a mix or a metric is adding files and
entries; nothing here names one.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_spec(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(rows: list, name: str, what: str) -> dict:
    for row in rows:
        if row["name"] == name:
            return row
    raise KeyError(f"BENCHMARK.json names no {what} {name!r}")


def load_cell(spec: dict, workload: str) -> dict:
    """The workload's entry with its configuration and mix loaded."""
    cell = dict(by_name(spec["workloads"], workload, "workload"))
    entry = by_name(spec["configs"], cell["config"], "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cell["config_data"] = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        cell["mix"] = json.load(f)
    return cell


def load_generator(name: str):
    return importlib.import_module(f"benchmark.generators.{name}")


def metrics_for(spec: dict, workload: str, kind: str) -> list:
    """The cell's metrics of ``end_to_end`` or ``per_layer``. An
    end-to-end metric that lists no ``workloads`` is every cell's; a
    per-layer metric that lists none is read in every cell that reports
    the end-to-end metric it moves."""
    e2e = [
        m for m in spec["end_to_end"]
        if "workloads" not in m or workload in m["workloads"]
    ]
    if kind == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [
        m for m in spec["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]


def load_reader(name: str):
    """``read`` of benchmark/metrics/<name>.py (a metric's name may hold
    dots, so the file is loaded by its path)."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def percentile(sorted_values: list, q: float) -> float:
    """Linear interpolation between closest ranks, as numpy's default."""
    if not sorted_values:
        raise ValueError("no samples")
    k = (len(sorted_values) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(sorted_values) - 1)
    return sorted_values[lo] + (sorted_values[hi] - sorted_values[lo]) * (k - lo)


def apply_pins(pins: dict) -> None:
    """Set the module attributes a configuration pins, such as
    ``cometbft_tpu.ops.ed25519.PAD_MIN``."""
    for dotted, value in pins.items():
        mod, attr = dotted.rsplit(".", 1)
        setattr(importlib.import_module(mod), attr, value)
