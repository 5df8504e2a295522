"""Per-pass timing of the decision step on the attached device.

Times each sub-pass of spatial_step (assign+count, handover
detect+compact, AOI plane, fan-out due, consume packing) as its own
256-iteration fused scan, so dispatch amortizes identically for every
pass and the numbers decompose the whole-step median from bench.py.
Guides kernel work: attack the biggest slice.

Run on the chip (no args); prints one JSON line. Exits non-zero on any
other platform.
"""

import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import _require_tpu
from channeld_tpu.ops.spatial_ops import (
    GridSpec,
    QuerySet,
    aoi_masks,
    assign_cells,
    cell_counts,
    compact_handovers,
    detect_handovers,
    fanout_due,
)
from channeld_tpu.ops.pallas_kernels import (
    aoi_masks_pallas,
    assign_and_count_pallas,
    pallas_available,
)

N, Q, S, C_SIDE, MAX_HO = 100_000, 1024, 100_000, 15, 4096
STEPS = 256


def timed_scan(name, fn, init_carry, results):
    """Median per-iteration ms of `fn` scanned STEPS times on device."""

    def body(carry, _):
        return fn(carry), None

    scanned = jax.jit(lambda c: jax.lax.scan(body, c, None, length=STEPS)[0])
    out = scanned(init_carry)  # compile + warm
    jax.block_until_ready(out)
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = scanned(out)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / STEPS * 1000)
    results[name] = round(float(np.median(samples)), 4)


def main() -> None:
    _require_tpu()
    grid = GridSpec(offset_x=-750.0, offset_z=-750.0, cell_w=100.0,
                    cell_h=100.0, cols=C_SIDE, rows=C_SIDE)
    rng = np.random.default_rng(0)
    pos = jnp.asarray(rng.uniform(-740, 740, (N, 3)).astype(np.float32))
    valid = jnp.ones(N, bool)
    qs = QuerySet(
        jnp.asarray(rng.integers(1, 4, Q).astype(np.int32)),
        jnp.asarray(rng.uniform(-700, 700, (Q, 2)).astype(np.float32)),
        jnp.asarray(np.full((Q, 2), 120.0, np.float32)),
        jnp.asarray(np.tile(np.array([0.0, 1.0], np.float32), (Q, 1))),
        jnp.asarray(np.full(Q, 0.6, np.float32)),
        None,
    )
    last = jnp.zeros(S, jnp.int32)
    interval = jnp.asarray(rng.integers(20, 100, S).astype(np.int32))
    active = jnp.ones(S, bool)

    dev = jax.devices()[0]
    results: dict = {"device": str(dev), "N": N, "Q": Q, "S": S,
                     "cells": C_SIDE * C_SIDE, "steps_per_scan": STEPS}

    # Carry-varied inputs: no iteration repeats the one before it.
    timed_scan(
        "assign_cells_xla_ms",
        lambda p: assign_cells(grid, p, valid).astype(jnp.float32)[:, None]
        * 0.0 + p + 0.001,
        pos, results)
    timed_scan(
        "assign_count_xla_ms",
        lambda p: (lambda c: p + jnp.float32(0.000001) *
                   cell_counts(c, grid.num_cells)[0])(
                       assign_cells(grid, p, valid)),
        pos, results)
    if pallas_available():
        def pallas_pass(p):
            cell, counts = assign_and_count_pallas(grid, p, valid)
            return p + jnp.float32(0.000001) * counts[0]
        timed_scan("assign_count_mosaic_ms", pallas_pass, pos, results)

        def aoi_pallas_pass(c):
            hit, dist = aoi_masks_pallas(
                grid, QuerySet(qs.kind, qs.center + c, qs.extent,
                               qs.direction, qs.angle, None))
            return c + jnp.float32(0.000001) * dist[0, 0]
        timed_scan("aoi_mosaic_ms", aoi_pallas_pass,
                   jnp.float32(0.0), results)

    def aoi_xla_pass(c):
        hit, dist = aoi_masks(
            grid, QuerySet(qs.kind, qs.center + c, qs.extent, qs.direction,
                           qs.angle, None))
        return c + jnp.float32(0.000001) * dist[0, 0]
    timed_scan("aoi_xla_ms", aoi_xla_pass, jnp.float32(0.0), results)

    def handover_pass(p):
        cell = assign_cells(grid, p, valid)
        prev = assign_cells(grid, p + 30.0, valid)
        mask = detect_handovers(prev, cell)
        count, rows, reported = compact_handovers(mask, prev, cell, MAX_HO)
        return p + jnp.float32(0.000001) * (count + rows[0, 0])
    timed_scan("handover_detect_compact_ms", handover_pass, pos, results)

    def due_pass(l):
        due, new_last = fanout_due(jnp.int32(1000), l, interval, active)
        packed = jnp.packbits(due)
        return new_last + packed[0].astype(jnp.int32) * 0
    timed_scan("fanout_due_pack_ms", due_pass, last, results)

    print(json.dumps(results))


if __name__ == "__main__":
    main()
