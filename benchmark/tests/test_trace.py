"""The reduction from a profiler trace to device numbers: on hand-made
planes, and on the small recorded traces kept in ``data/``."""

import glob
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000  # ns


def planes():
    ops = [("fusion.1", 10 * MS, 2 * MS), ("copy.2", 11 * MS, 2 * MS),
           ("fusion.1", 20 * MS, 1 * MS), ("scatter.3", 40 * MS, 4 * MS)]
    modules = [("jit_spatial_step(77)", 10 * MS, 3 * MS),
               ("jit_spatial_step(77)", 20 * MS, 1 * MS),
               ("jit_sim_step(5)", 40 * MS, 4 * MS)]
    host = [("python", [("tick", 0, 50 * MS)])]
    return [("/host:CPU", host),
            ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules),
                               ("Steps", [])])]


def test_busy_is_the_union_of_the_operations_intervals():
    assert trace.union_seconds([(0, 10), (5, 20), (30, 40)]) == 30e-9
    out = trace.reduce_planes(planes())
    # [10,13) overlapping pair, [20,21), [40,44): 3 + 1 + 4 ms
    assert out["busy_s"] == pytest.approx(0.008)
    assert out["window_s"] == pytest.approx(0.050)  # the host line spans it
    assert 100 * (1 - out["busy_s"] / out["window_s"]) == pytest.approx(84.0)


def test_time_and_count_of_each_program():
    out = trace.reduce_planes(planes())
    assert out["modules"]["jit_spatial_step"] == {"count": 2.0,
                                                  "seconds": pytest.approx(0.004)}
    assert out["modules"]["jit_sim_step"]["count"] == 1.0
    assert out["device_ops"][0] == ["scatter.3", pytest.approx(0.004)]
    assert out["device_ops"][1] == ["fusion.1", pytest.approx(0.003)]
    longest = out["idle_gaps"][0]
    assert longest[0] == "jit_spatial_step -> jit_sim_step"
    assert longest[1] == pytest.approx(0.019)


def test_two_chips_are_averaged():
    two = planes() + [("/device:TPU:1", [("XLA Ops", [("fusion.1", 0, 4 * MS)]),
                                        ("XLA Modules", [])])]
    out = trace.reduce_planes(two)
    assert out["device_planes"] == 2
    assert out["busy_s"] == pytest.approx((0.008 + 0.004) / 2)


def test_a_trace_with_no_device_plane_gives_no_device_number():
    out = trace.reduce_planes(planes()[:1])
    assert out == {"device_planes": 0}


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.xplane.pb"))))
def test_recorded_trace(path):
    """A trace recorded from the gateway, kept small; its expected
    reduction sits beside it."""
    done = subprocess.run(
        [sys.executable, trace.__file__, path], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(done.stdout.splitlines()[-1])
    with open(path.replace(".xplane.pb", ".expected.json")) as f:
        want = json.load(f)
    assert got["device_planes"] == want["device_planes"]
    if want["device_planes"]:
        assert got["busy_s"] == pytest.approx(want["busy_s"])
        assert got["window_s"] == pytest.approx(want["window_s"])
        assert 0.0 < got["busy_s"] <= got["window_s"]
        for name, entry in want["modules"].items():
            assert got["modules"][name]["count"] == entry["count"]
            assert got["modules"][name]["seconds"] == pytest.approx(
                entry["seconds"])
