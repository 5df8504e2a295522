"""``device_step_loop_ms`` on hand-made ``/metrics`` deltas: a program
that awaits its device step, and one (the parent of PR 26) that blocks in
it and records no ``step.await``."""
import os

import pytest

from benchmark.harness import driver

BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def stage(name, total_ms, count):
    labels = (("stage", name),)
    return {("tick_stage_ms_sum", labels): total_ms,
            ("tick_stage_ms_count", labels): count}


def read(metrics):
    reader = driver.load_file(
        os.path.join(BASE, "layer_metrics", "device_step_loop_ms.py"),
        "layer_metric_device_step_loop_ms")
    return reader.read({"metrics": metrics, "wall_s": 20.0, "trace": None,
                        "base": BASE})


def test_the_awaited_part_of_the_step_is_not_the_loops():
    # 300 steps of 21 ms from outside, 18.5 of them awaited.
    awaited = {**stage("device_step", 6300.0, 300),
               **stage("step.await", 5550.0, 300),
               **stage("step.begin", 450.0, 300)}
    assert read(awaited) == pytest.approx(2.5)
    # A direct caller among them awaited nothing: its whole step is the
    # loop's, and the mean says so.
    mixed = {**stage("device_step", 6300.0 + 16.0, 301),
             **stage("step.await", 5550.0, 300)}
    assert read(mixed) == pytest.approx((750.0 + 16.0) / 301)


@pytest.mark.parametrize("metrics", [
    stage("device_step", 3280.0, 200),                       # the parent
    {**stage("device_step", 3280.0, 200), **stage("step.await", 0.0, 0)},
    stage("step.await", 100.0, 10),                          # no step made
    {},
])
def test_nothing_to_read_is_left_out(metrics):
    assert read(metrics) is None


def test_the_contract_names_the_reader():
    import json

    with open(os.path.join(os.path.dirname(BASE), "BENCHMARK.json")) as f:
        contract = json.load(f)
    (entry,) = [m for m in contract["per_layer"]
                if m["name"] == "device_step_loop_ms"]
    assert entry == {
        "name": "device_step_loop_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "engine",
        "moves": "delivery_p50_ms", "workloads": ["npc-world-50k.roam"]}
    assert contract["per_layer"][-1] is entry  # appended, nothing moved
