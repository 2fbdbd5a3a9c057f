"""``val150.verify-only.mesh4`` is found by name like every cell: its
configuration, its mix and the mix's generator, the four ``mesh_*``
readers, and ``kernel_roofline.verify`` left to the one-chip cell (one
chip's peak under a dispatch that four chips verify reads four times
too high). The mix's tiny twin is ``tiny-commit-stream`` as it is.
"""

from benchmark import lookup

CELL = "val150.verify-only.mesh4"
MESH_METRICS = (
    "mesh_kernel_roofline.verify",
    "mesh_put_ms_per_dispatch.verify",
    "mesh_fetch_ms_per_dispatch.verify",
    "mesh_shard_skew_ms.verify",
)


def test_lookup_finds_the_cell_and_its_metrics():
    spec = lookup.load_spec()
    cell = lookup.load_cell(spec, CELL)
    assert cell["chips"] == 4 and cell["config"] == "val150-kvstore-mesh4"
    assert [w["name"] for w in spec["workloads"] if w["chips"] != 1] == [CELL]
    config, mix = cell["config_data"], cell["mix"]
    assert config["backend"] == "mesh" and config["validators"] == 150
    base = lookup.load_cell(spec, "val150.catchup")["config_data"]
    for key in ("validators", "voting_power", "key_type", "app", "chain_id",
                "commit_verification", "guarantees"):
        assert config[key] == base[key], key
    assert mix["generator"] == "commit_stream"
    assert callable(lookup.load_generator(mix["generator"]).Traffic)
    light = 2 * config["validators"] // 3 + 1
    assert mix["batch_commits"] * light == 51_712
    assert mix["pool_heights"] % mix["batch_commits"] == 0
    e2e = [m["name"] for m in lookup.metrics_for(spec, CELL, "end_to_end")]
    # not verify_batch_p95: four seeds spread it by 6.9% (PERF.md section 6)
    assert e2e == ["verify_rate", "setup_s"]
    per_layer = [m["name"] for m in lookup.metrics_for(spec, CELL, "per_layer")]
    assert [n for n in per_layer if n.startswith("mesh_")] == list(MESH_METRICS)
    assert "kernel_roofline.verify" not in per_layer
    assert "kernel_ms_per_dispatch.verify" in per_layer
    # the unlisted .verify metrics less the roofline, PR 27's stages, the four
    assert len([n for n in per_layer if n.endswith(".verify")]) == 7 + 10 + 4
    assert not [n for n in per_layer if n.endswith(".catchup")]
    # no other cell reads the mesh metrics, and the one-chip verify
    # cell keeps its own roofline
    for other in ("val150.catchup", "qa175.verify-only", "qa175.catchup"):
        names = [m["name"] for m in lookup.metrics_for(spec, other, "per_layer")]
        assert not [n for n in names if n.startswith("mesh_")], other
    assert "kernel_roofline.verify" in [
        m["name"] for m in lookup.metrics_for(spec, "qa175.verify-only", "per_layer")
    ]
