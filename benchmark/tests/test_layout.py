"""The layout comes from the run's seed, and the offered load is held
equal from seed to seed by count."""

import json
import os

import numpy as np
import pytest

from benchmark.harness.workers import client_centres, plan

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FILES = os.path.join(REPO, "benchmark", "configs", "files")


def spec(seed, **mix):
    with open(os.path.join(REPO, "benchmark", "traffic", "roam.json")) as f:
        roam = json.load(f)
    return {"scc": os.path.join(FILES, "spatial_tpu_benchmark.json"),
            "cell_start": 0x10000, "entity_start": 0x80000,
            "mix": dict(roam, **mix), "seed": seed, "entities": 256,
            "clients_total": 64, "radius": 3000.0, "seconds_total": 31.0,
            "window": (5.0, 25.0)}


def test_every_seed_is_another_layout_offering_the_same_count():
    a, b, again = plan(spec(3000000019)), plan(spec(7)), plan(spec(7))
    offered = spec(7)["mix"]["offered"]
    for p in (a, b):
        assert abs(p["deliveries_per_update"] / offered["deliveries_per_update"]
                   - 1.0) <= offered["within"]
    assert not np.array_equal(a["start"], b["start"])
    assert [c[:2] for c in a["centres"]] != [c[:2] for c in b["centres"]]
    assert np.array_equal(b["pos"], again["pos"])
    assert b["centres"] == again["centres"]
    # The deliveries due in the window differ by no more than the share
    # allowed on either side.
    counts = []
    for p in (a, b):
        watchers = np.zeros(p["grid"].num_cells, int)
        for _, _, cells in p["centres"]:
            watchers[list(cells)] += 1
        due = (p["due"] >= 5.0) & (p["due"] < 25.0)
        counts.append(watchers[p["cells"][due]].sum())
    assert abs(counts[0] / counts[1] - 1.0) <= 2.1 * offered["within"]


def test_a_sphere_keeps_64_float32_steps_from_every_cell_and_no_more():
    p = plan(spec(11))
    grid = p["grid"]
    slacks = [grid.sphere_cells(cx, cz, 3000.0)[1] for cx, cz, _ in p["centres"]]
    assert min(slacks) > grid.border_guard
    # bfloat16 resolves 64 to 128 units here: a margin of a few float32
    # steps leaves spheres that it moves across a cell's corner or edge.
    many = client_centres(grid, 400, 3000.0, [11, 0, 1])
    assert sum(grid.sphere_cells(cx, cz, 3000.0)[1] < 32.0
               for cx, cz, _ in many) > 40


def test_a_mix_without_an_offered_count_is_refused():
    bad = spec(1)
    del bad["mix"]["offered"]
    with pytest.raises(KeyError):
        plan(bad)


def test_an_offer_no_layout_meets_is_an_error_not_a_near_miss():
    with pytest.raises(ValueError, match="no layout of seed"):
        plan(spec(1, offered={"deliveries_per_update": 30.0, "within": 0.001}))
