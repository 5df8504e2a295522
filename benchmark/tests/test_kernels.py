"""Each kernel's operations and bytes against a hand count at a small
shape, and the roofline share they give."""

import importlib.util
import os

import pytest

BASE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = {"entities": 8, "queries": 2, "cells": 4, "subs": 16,
         "max_handovers": 3, "query_rows_max": 5}


def kernel(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BASE, "kernels", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_spatial_step_bytes():
    # read: positions 8x12, prev cell 8x4, valid 8x1; queries 2 x (kind 4,
    # centre 8, extent 8, direction 8, angle 4); subs 16 x (last 4,
    # interval 4, active 1).
    read = 96 + 32 + 8 + 2 * 32 + 16 * 9
    # written: cell_of and committed_prev 8x4 each; handover rows 3x12 and
    # their count 4; cell counts 4x4; interest 2x4x1 and dist 2x4x4; due
    # 16 and its packed form 2; the blob (1 + 9 + 4) x 4 + 2; new_last 16x4.
    write = 64 + 36 + 4 + 16 + 8 + 32 + 16 + 2 + (14 * 4 + 2) + 64
    assert kernel("spatial_step").bytes(SMALL) == read + write


def test_diff_query_masks_bytes():
    # read: baseline and this tick's masks, each 8 x (1 + 4); written: the
    # next baseline 8 x (1 + 4) and the blob (1 + 3 x 5) x 4.
    assert kernel("diff_query_masks").bytes(SMALL) == 80 + 40 + 64
    # The row budget is clamped to queries x cells, as the kernel does.
    assert kernel("diff_query_masks").bytes(dict(SMALL, query_rows_max=99)) \
        == 80 + 40 + 4 * (1 + 3 * 8)


def test_sim_step_bytes():
    # read: positions, velocity, target 8x12 each, state 8x4, agent 8x1,
    # flee mask 4; written: the four columns again.
    assert kernel("sim_step").bytes(SMALL) == (8 * 41 + 4) + 8 * 40


@pytest.mark.parametrize("name", ["spatial_step", "diff_query_masks", "sim_step"])
def test_each_kernel_names_its_program_and_counts_operations(name):
    k = kernel(name)
    assert k.PROGRAM == "jit_" + name
    assert k.ops(SMALL) > 0
    assert k.ops({**SMALL, "entities": 16, "queries": 4}) > k.ops(SMALL)


def test_roofline_share_from_a_reduced_trace():
    from benchmark.harness import driver

    shapes = {"entities": 131072, "queries": 4096, "cells": 225,
              "subs": 65536, "max_handovers": 4096, "query_rows_max": 8192}
    ctx = {"base": BASE, "shapes": shapes,
           "peak": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
           "trace": {"modules": {"jit_spatial_step": {"count": 10,
                                                      "seconds": 0.02}}}}
    share = driver.roofline_pct(ctx, "spatial_step")
    least = kernel("spatial_step").bytes(shapes) / 819e9  # bytes bind it
    assert share == pytest.approx(100.0 * least * 10 / 0.02)
    assert 0.0 < share < 1.0
    # A kernel the trace does not hold gives nothing, never 0.
    assert driver.roofline_pct(ctx, "sim_step") is None
    assert driver.roofline_pct(dict(ctx, trace=None), "spatial_step") is None
