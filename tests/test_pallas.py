"""Pallas Mosaic kernels vs the XLA reference.

Interpret-mode tests run everywhere; the on-device parity tests compile
the kernels with Mosaic at the gateway's default widths and need a TPU
(``CHTPU_TEST_TPU=1 python -m pytest tests/test_pallas.py -k on_device``)."""

import numpy as np
import pytest

import jax.numpy as jnp

from channeld_tpu.ops.pallas_kernels import (
    aoi_masks_pallas,
    assign_and_count_pallas,
    pallas_available,
)
from channeld_tpu.ops.spatial_ops import (
    AOI_SPOTS,
    GridSpec,
    QuerySet,
    aoi_masks,
    assign_cells,
    cell_counts,
)

GRID = GridSpec(offset_x=-150.0, offset_z=-150.0, cell_w=100.0, cell_h=100.0,
                cols=3, rows=3)
BENCH_GRID = GridSpec(offset_x=-15000.0, offset_z=-15000.0, cell_w=2000.0,
                      cell_h=2000.0, cols=15, rows=15)


def random_queries(rng, q, grid, with_spots=False) -> QuerySet:
    spot_dist = None
    kinds = rng.integers(0, 4, q).astype(np.int32)  # NONE..CONE
    if with_spots:
        kinds[:: max(q // 7, 1)] = AOI_SPOTS
        spot_dist = np.full((q, grid.num_cells), -1, np.int32)
        hits = rng.random((q, grid.num_cells)) < 0.2
        spot_dist[hits] = rng.integers(0, 5, hits.sum())
        spot_dist = jnp.asarray(spot_dist)
    lo_x = grid.offset_x - grid.cell_w
    hi_x = grid.offset_x + grid.cell_w * (grid.cols + 1)
    direction = rng.normal(size=(q, 2)).astype(np.float32)
    direction /= np.linalg.norm(direction, axis=1, keepdims=True)
    return QuerySet(
        kind=jnp.asarray(kinds),
        center=jnp.asarray(
            rng.uniform(lo_x, hi_x, size=(q, 2)).astype(np.float32)
        ),
        extent=jnp.asarray(
            rng.uniform(1.0, grid.cell_w * 4, size=(q, 2)).astype(np.float32)
        ),
        direction=jnp.asarray(direction),
        angle=jnp.asarray(rng.uniform(0.1, 1.5, q).astype(np.float32)),
        spot_dist=spot_dist,
    )


def test_pallas_assign_count_matches_xla():
    rng = np.random.default_rng(3)
    n = 5000  # not a TILE multiple: exercises padding
    pts = rng.uniform(-200, 200, size=(n, 3)).astype(np.float32)
    valid = rng.random(n) > 0.1
    cell_ref = np.asarray(assign_cells(GRID, jnp.asarray(pts), jnp.asarray(valid)))
    counts_ref = np.asarray(cell_counts(jnp.asarray(cell_ref), GRID.num_cells))

    cell, counts = assign_and_count_pallas(
        GRID, jnp.asarray(pts), jnp.asarray(valid), interpret=True
    )
    assert np.array_equal(np.asarray(cell), cell_ref)
    assert np.array_equal(np.asarray(counts), counts_ref)


@pytest.mark.parametrize("with_spots", [False, True])
@pytest.mark.parametrize("grid", [GRID, BENCH_GRID], ids=["3x3", "bench15x15"])
def test_pallas_aoi_masks_match_xla(grid, with_spots):
    """The Mosaic AOI kernel produces the same interest/dist planes as
    spatial_ops.aoi_masks for every query kind, incl. query-count padding
    (29 is not a sublane multiple) and the spots-table overlay."""
    rng = np.random.default_rng(11)
    queries = random_queries(rng, 29, grid, with_spots)
    ref_hit, ref_dist = aoi_masks(grid, queries)
    hit, dist = aoi_masks_pallas(grid, queries, interpret=True)
    assert np.array_equal(np.asarray(hit), np.asarray(ref_hit))
    # Distances must agree wherever there is interest (outside, the host
    # never reads them).
    mask = np.asarray(ref_hit)
    assert np.array_equal(np.asarray(dist)[mask], np.asarray(ref_dist)[mask])


# ---- on-device parity at the gateway's default widths ----------------------

needs_tpu = pytest.mark.skipif(
    not pallas_available(), reason="the default backend is not a TPU"
)


@needs_tpu
def test_pallas_aoi_masks_on_device():
    """4,096 query rows (core/settings.py tpu_query_capacity) x 225 cells."""
    rng = np.random.default_rng(5)
    queries = random_queries(rng, 4096, BENCH_GRID)
    ref_hit, ref_dist = aoi_masks(BENCH_GRID, queries)
    hit, dist = aoi_masks_pallas(BENCH_GRID, queries)
    mask = np.asarray(ref_hit)
    differ = np.argwhere(np.asarray(hit) != mask)
    assert differ.size == 0, (
        f"{len(differ)} of {mask.size} (query, cell) hits differ, first "
        f"{differ[:5].tolist()}"
    )
    assert np.array_equal(np.asarray(dist)[mask], np.asarray(ref_dist)[mask])


@needs_tpu
def test_pallas_assign_count_on_device():
    """131,072 entity slots (tpu_entity_capacity), 100,000 of them live."""
    rng = np.random.default_rng(6)
    n = 1 << 17
    pts = rng.uniform(-16000, 16000, size=(n, 3)).astype(np.float32)
    valid = np.arange(n) < 100_000
    cell, counts = assign_and_count_pallas(
        BENCH_GRID, jnp.asarray(pts), jnp.asarray(valid)
    )
    cell_ref = assign_cells(BENCH_GRID, jnp.asarray(pts), jnp.asarray(valid))
    assert np.array_equal(np.asarray(cell), np.asarray(cell_ref))
    counts_ref = cell_counts(cell_ref, BENCH_GRID.num_cells)
    assert np.array_equal(np.asarray(counts), np.asarray(counts_ref))
