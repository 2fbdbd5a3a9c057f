"""``val150.verify-only.512`` is found by name like every cell: val150's
chain and keys, one chip, 512-commit batches, and the pin that puts
every dispatch at 65,536 lanes, the width at which the program's
default Pallas ladder runs. The accepted cell below the threshold,
``qa175.verify-only`` at 16,384 lanes, keeps the XLA ladder.
"""

from benchmark import lookup

CELL = "val150.verify-only.512"
LADDER_METRICS = (
    "ladder_ms_per_dispatch.verify",
    "ladder_roofline.verify",
    "pallas_dispatch_share.verify",
)


def test_lookup_finds_the_w512_cell_and_its_metrics():
    spec = lookup.load_spec()
    cell = lookup.load_cell(spec, CELL)
    assert cell["chips"] == 1 and cell["config"] == "val150-kvstore-w512"
    assert cell["traffic"] == "commit-stream-512"
    config, mix = cell["config_data"], cell["mix"]
    assert config["backend"] == "tpu"
    assert config["pins"] == {"cometbft_tpu.ops.ed25519.PAD_MIN": 65_536}
    base = lookup.load_cell(spec, "val150.catchup")["config_data"]
    for key in ("validators", "voting_power", "key_type", "app", "chain_id",
                "commit_verification", "guarantees"):
        assert config[key] == base[key], key
    assert mix["generator"] == "commit_stream"
    light = 2 * config["validators"] // 3 + 1
    assert mix["batch_commits"] * light == 51_712
    e2e = [m["name"] for m in lookup.metrics_for(spec, CELL, "end_to_end")]
    # not verify_batch_p95: the mesh cell's batch, off that list for its spread
    assert e2e == ["verify_rate", "setup_s"]
    per_layer = [m["name"] for m in lookup.metrics_for(spec, CELL, "per_layer")]
    assert set(LADDER_METRICS) <= set(per_layer)
    assert not [n for n in per_layer if n.startswith("mesh_")]
    assert "kernel_roofline.verify" in per_layer
    assert not [n for n in per_layer if n.endswith(".catchup")]
    # the three are this cell's alone
    for other in spec["workloads"]:
        if other["name"] != CELL:
            names = [m["name"] for m in lookup.metrics_for(spec, other["name"], "per_layer")]
            assert not set(LADDER_METRICS) & set(names), other["name"]
