"""chip_smoke.py walked on the CPU: the same flow at a tiny size against a
real gateway child, and the ways it must refuse to pass without a chip."""

import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_tiny_world_passes_every_check_but_the_platform(tmp_path, monkeypatch):
    # The cache placed from outside: the gateway child inherits the
    # variable, and nothing lands in the checkout's own .jax_cache.
    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    tiny = chip_smoke.Sizes(
        scc="config/spatial_tpu_4x4.json", agents=48, wire_entities=16,
        clients=2, crossings=4, radius=75.0, move_s=5.0,
    )
    report = chip_smoke.run(tiny, str(tmp_path / "out"))

    assert all(report["checks"].values()), report["checks"]
    assert report["cache_dir"] == str(cache)
    assert report["cache_entries_cold_boot"] > 0
    assert report["cache_new_entries_warm_boot"] == 0
    assert report["native_codec"] is True
    assert report["tpu_entities"] == 48 + 16
    assert report["reduced"], "a tiny run must say what it cut"

    assert report["platform"] == "cpu"
    failures = chip_smoke.verify(report)
    assert "platform is 'cpu', not 'tpu'" in failures
    # ... and for the platform alone: the same report from a chip passes.
    on_chip = dict(report, platform="tpu", use_pallas=True)
    assert chip_smoke.verify(on_chip) == []

    # The last line of stdout carries the verdict and the device, no more.
    assert json.loads(chip_smoke.result_line(report, ok=False)) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": report["device_kind"],
                   "count": report["device_count"]},
    }
    assert isinstance(report["device_kind"], str)
    assert isinstance(report["device_count"], int)


def _run_script(script: str, cwd: str, env: dict):
    t0 = time.monotonic()
    done = subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=60,
    )
    return done, time.monotonic() - t0


def test_cpu_platform_is_refused_before_any_gateway_starts():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done, took = _run_script(os.path.join(REPO, "chip_smoke.py"), REPO, env)
    assert done.returncode != 0
    assert done.stdout == ""  # no result line
    assert "JAX_PLATFORMS=cpu" in done.stderr
    assert took < 10, "it must not have built or booted anything"


def test_the_script_alone_is_refused(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    done, _ = _run_script("chip_smoke.py", str(tmp_path), env)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "the program is not here" in done.stderr
