"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware; env vars must be set before jax imports.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests run on the CPU, chosen the one way the program allows: JAX_PLATFORMS.
# CHTPU_TEST_TPU=1 leaves the platform alone so the on-device parity tests
# can reach a chip:
#   CHTPU_TEST_TPU=1 python -m pytest tests/test_pallas.py -k on_device
if os.environ.get("CHTPU_TEST_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soaks/benches excluded from tier-1 (-m 'not slow')",
    )

# Build the native codec if a toolchain exists, where no library lies in
# the tree or the one that does is older than its source (as
# benchmark/harness/driver.py:build_native does): a working tree that
# kept an older library would run the native-path tests against a codec
# without the newest functions, or skip them. Cheap (~5s) and idempotent;
# the tests skip gracefully if the build fails (e.g. no g++).
_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_native = os.path.join(_repo, "channeld_tpu", "native")
_codec_built = [
    os.path.getmtime(os.path.join(_native, f)) for f in os.listdir(_native)
    if f.startswith("_codec") and f.endswith(".so")
]
if not _codec_built or min(_codec_built) < os.path.getmtime(
    os.path.join(_native, "codec.cc")
):
    import subprocess

    subprocess.run(
        ["sh", os.path.join(_repo, "scripts", "build_native.sh")],
        cwd=_repo, capture_output=True, timeout=120, check=False,
    )


@pytest.fixture(autouse=True)
def _fresh_globals(tmp_path):
    """Reset process-wide singletons between tests. The flight recorder
    stays enabled (it is always-on in production too) but dumps under
    the test's tmp dir and starts each test with empty rings — anomaly
    auto-dumps from one test must not land in the repo's profiles/ or
    slow a later timing-sensitive test with a full-ring freeze.

    Runtime thread-affinity assertions (core/affinity.py,
    doc/concurrency.md) are ARMED for every tier-1 test: any code that
    runs on the wrong thread relative to the declared thread model
    records a violation, and the teardown below fails the offending
    test with it. Off in production by default (-debug-affinity arms a
    live gateway)."""
    from channeld_tpu.core import device_guard, events, overload, settings, tracing
    from channeld_tpu.core.affinity import affinity
    from channeld_tpu.spatial import balancer as balancer_mod

    tracing.recorder.configure(dump_path=str(tmp_path))
    affinity.arm(strict=False)
    yield
    from channeld_tpu.core import opshttp as opshttp_mod
    from channeld_tpu.core import slo as slo_mod
    from channeld_tpu.core import wal as wal_mod
    from channeld_tpu.federation import obs as obs_mod

    violations = list(affinity.violations)
    affinity.disarm()
    events.reset_all()
    settings.reset_global_settings()
    overload.reset_overload()
    balancer_mod.reset_balancer()
    from channeld_tpu.spatial import partition as partition_mod

    partition_mod.reset_partition()
    device_guard.reset_device_guard()
    tracing.reset_tracing()
    wal_mod.reset_wal()
    # SLO/fleet-obs state and any ops HTTP server a test started are
    # torn down too (tests bind ephemeral ports via serve_ops(0)).
    slo_mod.reset_slo()
    obs_mod.reset_fleet_obs()
    opshttp_mod.reset_ops()
    from channeld_tpu.sim import plane as sim_plane_mod

    sim_plane_mod.reset_sim()
    assert not violations, (
        "runtime thread-affinity violations (doc/concurrency.md): "
        f"{violations}"
    )
