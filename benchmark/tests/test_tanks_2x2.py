"""The cell ``tanks-2x2.drive``: found by name with no file of the harness
edited; upstream's Mirror-tanks deployment at its own hifi settings (the
4x4 world owned 2x2, 20 ms for every channel type, no sim plane), at the
device widths of the other files; and the metrics it reports."""

import json
import os

from benchmark.harness import driver

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NOT_HERE = {"sim_step_roofline", "census_stall_ms",  # no sim plane
            "device_step_loop_ms"}  # test_device_step_loop_ms.py: one cell
NEW = {"fanout_sends_per_encode", "send_pump_busy_pct"}


def test_the_cell_finds_its_files():
    cell = driver.load_cell(REPO, "tanks-2x2.drive")
    old = driver.load_cell(REPO, "npc-world-50k.roam")
    assert cell["chips"] == 1
    config, mix = cell["config"], cell["mix"]
    assert config["populations"] == {"sim_agents": 0, "wire_entities": 256,
                                     "clients": 256, "client_radius": 10.0}
    for key in ("device", "guarantees"):
        assert config[key] == old["config"][key], key
    assert "settings" not in config and "-sim" not in config["gateway_argv"]
    assert config["world"]["scc"] == "benchmark/configs/files/spatial_tpu_4x4.json"
    assert mix["generator"] == "walk" and mix["frame_ms"] == 20
    assert mix["speed"] == 12.0 and mix["warmup_s"] == mix["drain_s"] == 5.0
    # A rate under the 2 updates/s at which the parent is over its knee
    # (the ledger's PR 33 line), on a quarter of an update a second.
    assert 0 < mix["rate"] < 2 and mix["rate"] * 4 == int(mix["rate"] * 4)
    assert 20 <= mix["offered"]["deliveries_per_update"] <= 30
    assert mix["offered"]["within"] == 0.0015
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in old["end_to_end"]]


def test_the_cell_reports_the_50k_cells_metrics_but_three_and_two_new_ones():
    cell = driver.load_cell(REPO, "tanks-2x2.drive")
    names = [m["name"] for m in cell["per_layer"]]
    old = [m["name"] for m in
           driver.load_cell(REPO, "npc-world-50k.roam")["per_layer"]]
    assert NEW <= set(old)  # the old cells report the new metrics too
    assert names == [n for n in old if n not in NOT_HERE]
    for name in names:
        assert os.path.exists(os.path.join(
            cell["base"], "layer_metrics", name + ".py"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    # New entries go after those already there: the driver reads one put
    # anywhere else as a change to what was there. This cell's two follow
    # device_step_loop_ms, in their order.
    names = [m["name"] for m in per_layer]
    at = names.index("device_step_loop_ms")
    assert names[at:at + 3] == [
        "device_step_loop_ms", "fanout_sends_per_encode", "send_pump_busy_pct"]
    for m in per_layer[at + 1:at + 3]:
        assert m["workloads"] == ["npc-world-50k.roam", "npc-world-100k.roam",
                                  "tanks-2x2.drive"]


def test_the_source_is_one_string_in_both_places():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        contract = json.load(f)
    (entry,) = [c for c in contract["configs"] if c["name"] == "tanks-2x2"]
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for part in ("examples/unity-mirror-tanks", "spatial_static_2x2.json",
                 "4x4 cells of 50 units", "2x2 servers", "256 sim-clients",
                 "BASELINE.json config 2", "channel_settings_hifi.json",
                 "20 ms"):
        assert part in entry["source"], part
    assert entry["reduced"] == ["update_rate"] == list(config["reduced"])


def test_the_hifi_copy_is_the_programs_file():
    with open(os.path.join(REPO, "config", "channel_settings_hifi.json"), "rb") as f:
        theirs = f.read()
    with open(os.path.join(REPO, "benchmark", "configs", "files",
                           "channel_settings_hifi.json"), "rb") as f:
        assert f.read() == theirs


def test_the_gateway_accepts_the_configurations_argv():
    """The program's own parser takes every flag the file gives it, and
    lands on the deployment the file states: 20 ms ticks and fan-out for
    every channel type, no sim plane, the 4x4 world owned 2x2."""
    from channeld_tpu.core.settings import GlobalSettings
    from channeld_tpu.core.types import ChannelType

    config = driver.load_cell(REPO, "tanks-2x2.drive")["config"]
    s = GlobalSettings()
    cwd = os.getcwd()
    os.chdir(REPO)  # the argv's paths are relative to the checkout
    try:
        s.parse_flags(config["gateway_argv"])
        for kind in ChannelType:
            if kind == ChannelType.UNKNOWN:
                continue
            settings = s.get_channel_settings(kind)
            assert settings.tick_interval_ms == 20, kind
            assert settings.default_fanout_interval_ms == 20, kind
        with open(s.spatial_controller_config) as f:
            world = json.load(f)
    finally:
        os.chdir(cwd)
    assert not s.sim_enabled
    assert s.tpu_entity_capacity == config["device"]["entity_capacity"]
    assert s.tpu_query_capacity == config["device"]["query_capacity"]
    assert world["SpatialControllerType"] == "TPUSpatialController"
    assert {k: world["Config"][k] for k in (
        "GridCols", "GridRows", "GridWidth", "GridHeight", "ServerCols",
        "ServerRows", "WorldOffsetX", "ServerInterestBorderSize")} == {
        "GridCols": 4, "GridRows": 4, "GridWidth": 50, "GridHeight": 50,
        "ServerCols": 2, "ServerRows": 2, "WorldOffsetX": -100,
        "ServerInterestBorderSize": 1}
