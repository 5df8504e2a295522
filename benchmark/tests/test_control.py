"""What ``correct`` has to catch, planted under a whole run: the control
in the precision below the configuration's float32, and each fault the
cells can have. Every one must come out as not correct. (An exchange
between chips cannot be left out: the cells run on one.)"""

import json

import numpy as np
import pytest

from benchmark.tests import rehearse


def _bf16(a):
    bits = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def _seed_where_bf16_moves_a_crossing() -> int:
    """The schedule is known from the seed alone: take one in which
    rounding to bfloat16 puts some update on the other side of a border,
    so the control has to differ from the reference there."""
    import os

    from benchmark.harness.workers import plan

    spec = {"scc": os.path.join(rehearse.REPO, rehearse.ARGV[2]),
            "cell_start": 0x10000, "entity_start": 0x80000,
            "mix": rehearse.MIX, "entities": 24, "seconds_total": 13.0,
            "clients_total": 4, "radius": 40.0, "window": (2.0, 8.0)}
    for seed in range(1, 400):
        pl = plan(dict(spec, seed=seed))
        pos = pl["pos"][:16]  # in the warm-up or the window
        rounded = pl["grid"].cells_of(_bf16(pos[..., 0]), _bf16(pos[..., 1]))
        # (a point rounded off the world's edge is only dropped for a tick)
        if ((rounded != pl["cells"][:16]) & (rounded >= 0)).sum() >= 2:
            return seed
    raise AssertionError("no seed moves a crossing under bfloat16")


def test_the_bf16_control_is_not_correct(on_cpu):
    seed = _seed_where_bf16_moves_a_crossing()
    result = rehearse.rehearse(str(on_cpu / "root"), "tiny-world.amble",
                               seed=seed, control="bf16")
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert result["correct"] is False, checks
    assert (checks["handovers_lost"] or checks["handovers_early"]
            or checks["handovers_unpredicted"]), checks
    # ... and the same seed as the configuration states it is correct.
    sound = rehearse.rehearse(str(on_cpu / "sound"), "tiny-world.amble",
                              seed=seed)
    assert sound["correct"] is True, sound["checks"]


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "handovers_lost"),
    ("half_batch", "handovers_lost"),
    ("answer_altered", "handovers_unpredicted"),
])
def test_a_broken_timed_path_is_not_correct(on_cpu, fault, caught_by):
    result = rehearse.rehearse(str(on_cpu / "root"), "tiny-tanks.amble",
                               fault=fault)
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert result["correct"] is False, checks
    assert checks[caught_by] > 0, checks
    assert result["failed"] > 0
    json.dumps(result)  # a failing run must still print its line
