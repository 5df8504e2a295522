"""The whole flow at a tiny size on the CPU, for both kinds of
configuration, and the ways a run must refuse to pass without a chip."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.tests import rehearse

REPO = rehearse.REPO
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("cell,trace", [("tiny-world.amble", True),
                                        ("tiny-tanks.amble", False)])
def test_tiny_cell_is_correct_and_names_the_cpu(on_cpu, cell, trace):
    result = rehearse.rehearse(str(on_cpu / "root"), cell, trace=trace)
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert result["correct"] is True, checks
    assert not any(checks.values()), checks
    assert all(v["limit"] == 0 for v in result["checks"].values())
    assert [k for k in result if k in RESULT_KEYS] == RESULT_KEYS
    assert list(result)[-1] == "checks"  # it comes last in the line
    assert result["attempted"] > 100 and result["failed"] == 0
    # A rehearsal can never pass as a chip run.
    assert result["device"]["platform"] == "cpu"
    assert result["info"]["crossings_in_window"] > 5
    metrics = result["metrics"]
    assert all(set(m) == {"value", "unit"} for m in metrics.values())
    if trace:
        # Per-layer metrics; no device plane on the CPU, so nothing read
        # from the trace is reported, least of all as 0.
        assert {"global_tick_hz", "device_step_ms", "compiles_in_window",
                "generator_late_pct", "handover_p95_ms",
                "cell_row_stale_p50_ms"} <= set(metrics)
        assert not [m for m in metrics if m.endswith("_roofline")]
        assert "device_idle_pct" not in metrics and "busy_s" not in result["device"]
        assert metrics["compiles_in_window"]["value"] == 0
        # The per-layer metric that make_root dropped in as a new file,
        # and the tiny configuration, mix and cell, were all found.
        assert metrics["channel_ticks_hz"]["value"] > 0
    else:
        assert set(metrics) == {"delivery_p50_ms", "delivery_p95_ms",
                                "deliveries_per_s", "setup_s"}
        assert result["info"]["handover_p95_ms"] > 0
        assert all(m["value"] > 0 for m in metrics.values())


def test_new_files_and_entries_are_found_with_no_file_edited(tmp_path):
    from benchmark.harness import driver

    root = rehearse.make_root(str(tmp_path))
    cell = driver.load_cell(root, "tiny-tanks.amble")
    assert cell["mix"]["generator"] == "walk" and cell["mix"]["rate"] == 2
    assert cell["config"]["populations"]["wire_entities"] == 16
    names = [m["name"] for m in cell["per_layer"]]
    assert "channel_ticks_hz" in names and "sim_step_roofline" not in names
    assert "sim_step_roofline" in [
        m["name"] for m in driver.load_cell(root, "tiny-world.amble")["per_layer"]]
    # ... and the repo's own files under the copy are byte for byte the
    # repo's: nothing was edited to make room.
    for rel in ("run.py", "harness/driver.py", "traffic/roam.json",
                "layer_metrics/global_tick_hz.py", "generators/walk.py"):
        with open(os.path.join(REPO, "benchmark", rel), "rb") as a, \
                open(os.path.join(root, "benchmark", rel), "rb") as b:
            assert a.read() == b.read()


def _run(args, cwd, env):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


ARGS = ["--workload", "npc-world-50k.roam", "--seed", "3000000019",
        "--seconds", "1", "--trace", "0"]


def test_cpu_platform_is_refused_before_anything_starts():
    done = _run([os.path.join(REPO, "benchmark", "run.py"), *ARGS], REPO,
                dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0 and done.stdout == ""
    assert "JAX_PLATFORMS=cpu" in done.stderr


def test_the_benchmark_alone_is_refused(tmp_path):
    import shutil

    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    done = _run(["benchmark/run.py", *ARGS], str(tmp_path), env)
    assert done.returncode != 0 and done.stdout == ""
    assert "the program is not here" in done.stderr


def test_a_gateway_on_the_cpu_is_refused_after_boot(on_cpu, monkeypatch):
    """With JAX_PLATFORMS unset JAX may fall back to the CPU with only a
    warning; the run must see it in /introspect and stop."""
    from benchmark.harness import driver
    from benchmark.harness.gateway import BenchFailure

    cell = driver.load_cell(rehearse.make_root(str(on_cpu / "root")),
                            "tiny-tanks.amble")
    with pytest.raises(BenchFailure, match="platform 'cpu'"):
        driver.run_cell(cell, 1, 1.0, False, 0.0)  # need_platform="tpu"
