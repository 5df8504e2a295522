"""The cell ``npc-world-100k.roam``: found by name with no file of the
harness edited, the 50K deployment's files and widths at twice the
population with 256 of the agents channel-backed, and an argv the
commit before PR 27 starts too (the agents' mode goes through the
launcher's ``settings``, as in the 50K file)."""

import json
import os

from benchmark.harness import driver

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_the_cell_finds_its_files():
    cell = driver.load_cell(REPO, "npc-world-100k.roam")
    old = driver.load_cell(REPO, "npc-world-50k.roam")
    assert cell["chips"] == 1 and cell["mix"] == old["mix"]
    config = cell["config"]
    assert config["populations"] == dict(old["config"]["populations"],
                                         sim_agents=100000)
    for key in ("device", "guarantees", "workers", "world"):
        assert config[key] == old["config"][key], key
    assert config["settings"] == {"sim_channel_agents": 256}
    assert old["config"]["settings"] == {"sim_channel_agents": 0}
    argv, old_argv = config["gateway_argv"], old["config"]["gateway_argv"]
    assert argv[argv.index("-sim-agents") + 1] == "100000"
    # Nothing but the population differs on the command line.
    at = argv.index("-sim-agents") + 1
    assert argv[:at] + argv[at + 1:] == old_argv[:at] + old_argv[at + 1:]
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in old["end_to_end"]]
    # Every per-layer metric of the 50K cell but PR 26's, whose entry
    # test_device_step_loop_ms.py holds as it is: one cell, and last.
    names = [m["name"] for m in cell["per_layer"]]
    assert names == [m["name"] for m in old["per_layer"]
                     if m["name"] != "device_step_loop_ms"]
    for name in names:
        assert os.path.exists(os.path.join(
            cell["base"], "layer_metrics", name + ".py"))


def test_the_source_is_one_string_in_both_places():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        contract = json.load(f)
    (entry,) = [c for c in contract["configs"] if c["name"] == "npc-world-100k"]
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"])


def test_the_gateway_accepts_the_configurations_argv():
    """The program's own parser takes every flag the file gives it, and
    lands on the deployment the file states."""
    from channeld_tpu.core.settings import GlobalSettings

    config = driver.load_cell(REPO, "npc-world-100k.roam")["config"]
    s = GlobalSettings()
    cwd = os.getcwd()
    os.chdir(REPO)  # the argv's paths are relative to the checkout
    try:
        s.parse_flags(config["gateway_argv"])
    finally:
        os.chdir(cwd)
    assert s.sim_enabled and s.sim_agents == 100000
    assert s.tpu_entity_capacity == config["device"]["entity_capacity"]
    assert s.sim_census_every_ticks == 50
    for field in config["settings"]:
        assert hasattr(s, field), field
