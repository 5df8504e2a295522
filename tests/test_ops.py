"""TPU decision-plane kernels vs the host semantics (CPU, 8 virtual devices)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from channeld_tpu.ops.engine import SpatialEngine
from channeld_tpu.ops.spatial_ops import (
    AOI_BOX,
    AOI_CONE,
    AOI_SPHERE,
    GridSpec,
    QuerySet,
    aoi_masks,
    assign_cells,
    cell_counts,
    fanout_due,
)
from channeld_tpu.spatial.controller import SpatialInfo
from channeld_tpu.spatial.grid import StaticGrid2DSpatialController

START = 0x10000

GRID = GridSpec(offset_x=-150.0, offset_z=-150.0, cell_w=100.0, cell_h=100.0,
                cols=3, rows=3)


def host_controller() -> StaticGrid2DSpatialController:
    ctl = StaticGrid2DSpatialController()
    ctl.load_config(dict(
        WorldOffsetX=GRID.offset_x, WorldOffsetZ=GRID.offset_z,
        GridWidth=GRID.cell_w, GridHeight=GRID.cell_h,
        GridCols=GRID.cols, GridRows=GRID.rows,
        ServerCols=1, ServerRows=1, ServerInterestBorderSize=1,
    ))
    return ctl


def test_assign_cells_matches_host_reference():
    ctl = host_controller()
    rng = np.random.default_rng(0)
    pts = rng.uniform(-200, 200, size=(512, 3)).astype(np.float32)
    valid = np.ones(512, bool)
    cells = np.asarray(assign_cells(GRID, jnp.asarray(pts), jnp.asarray(valid)))
    for p, c in zip(pts, cells):
        try:
            expected = ctl.get_channel_id(SpatialInfo(float(p[0]), 0, float(p[2]))) - START
        except ValueError:
            expected = -1
        assert c == expected, p


def test_aoi_sphere_superset_of_host_sampling():
    """Device masks = exact overlap; must cover every host-sampled cell."""
    from channeld_tpu.protocol import spatial_pb2

    ctl = host_controller()
    rng = np.random.default_rng(1)
    for _ in range(20):
        cx, cz = rng.uniform(-140, 140, 2)
        r = rng.uniform(5, 200)
        q = spatial_pb2.SpatialInterestQuery(
            sphereAOI=spatial_pb2.SpatialInterestQuery.SphereAOI(
                center=spatial_pb2.SpatialInfo(x=cx, z=cz), radius=r
            )
        )
        host_cells = {k - START for k in ctl.query_channel_ids(q)}
        queries = QuerySet(
            kind=jnp.array([AOI_SPHERE]),
            center=jnp.array([[cx, cz]], jnp.float32),
            extent=jnp.array([[r, 0]], jnp.float32),
            direction=jnp.array([[1.0, 0.0]], jnp.float32),
            angle=jnp.array([0.0], jnp.float32),
        )
        hit, dist = aoi_masks(GRID, queries)
        device_cells = set(np.nonzero(np.asarray(hit[0]))[0].tolist())
        assert host_cells <= device_cells, (cx, cz, r, host_cells, device_cells)
        # Distance metric agrees on the query's own cell.
        own = ctl.get_channel_id(SpatialInfo(cx, 0, cz)) - START
        assert int(dist[0, own]) == 0


def test_aoi_cone_narrow_band():
    # Narrow cone along +X from the center of the bottom-left cell: the
    # bottom row only (mirrors the host geometry test expectations).
    queries = QuerySet(
        kind=jnp.array([AOI_CONE]),
        center=jnp.array([[-100.0, -100.0]], jnp.float32),
        extent=jnp.array([[1000.0, 0.0]], jnp.float32),
        direction=jnp.array([[1.0, 0.0]], jnp.float32),
        angle=jnp.array([0.1], jnp.float32),
    )
    hit, _ = aoi_masks(GRID, queries)
    assert set(np.nonzero(np.asarray(hit[0]))[0].tolist()) == {0, 1, 2}


def test_fanout_due_window_advance():
    last = jnp.array([0, 0, 40], jnp.int32)
    interval = jnp.array([50, 100, 50], jnp.int32)
    active = jnp.array([True, True, False])
    due, new_last = fanout_due(jnp.int32(60), last, interval, active)
    assert due.tolist() == [True, False, False]
    # Window advances by one interval, not to `now`.
    assert new_last.tolist() == [50, 0, 40]


def test_engine_tick_handover_and_interest():
    eng = SpatialEngine(GRID, entity_capacity=64, query_capacity=8,
                        sub_capacity=8, max_handovers=8)
    eng.add_entity(1001, -100, 0, -100)  # cell 0
    eng.add_entity(1002, 0, 0, 0)  # cell 4
    eng.set_query(7, AOI_SPHERE, (0.0, 0.0), (40.0, 0.0))
    s = eng.add_subscription(interval_ms=50, first_due_ms=0)

    r1 = eng.tick(now_ms=0)
    assert eng.handover_list(r1) == []  # first assignment: prev=-1, no crossing
    counts = np.asarray(r1["cell_counts"])
    assert counts[0] == 1 and counts[4] == 1
    assert eng.interested_cells(r1, 7) == {4: 0}

    # Entity 1001 moves two cells over; sub becomes due.
    eng.update_entity(1001, 100, 0, -100)  # cell 2
    r2 = eng.tick(now_ms=60)
    assert eng.handover_list(r2) == [(1001, 0, 2)]
    assert bool(np.asarray(r2["due"])[s])

    # Removing the entity frees its slot and drops it from the counts.
    eng.remove_entity(1001)
    r3 = eng.tick(now_ms=70)
    counts = np.asarray(r3["cell_counts"])
    assert counts[2] == 0 and counts.sum() == 1


def test_sharded_step_matches_single_device():
    from channeld_tpu.parallel.mesh import (
        build_sharded_step,
        make_mesh,
        sharded_spatial_step,
    )

    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    mesh = make_mesh()
    n = 64  # 8 per shard
    rng = np.random.default_rng(2)
    pts = rng.uniform(-140, 140, size=(n, 3)).astype(np.float32)
    valid = np.ones(n, bool)
    prev = np.asarray(assign_cells(GRID, jnp.asarray(pts), jnp.asarray(valid)))
    moved = pts.copy()
    moved[:8, 0] += 120  # force some crossings
    queries = QuerySet(
        kind=jnp.array([AOI_SPHERE, 0], jnp.int32),
        center=jnp.array([[0, 0], [0, 0]], jnp.float32),
        extent=jnp.array([[80, 0], [0, 0]], jnp.float32),
        direction=jnp.array([[1, 0], [1, 0]], jnp.float32),
        angle=jnp.zeros(2, jnp.float32),
    )
    sub_state = (
        jnp.zeros(4, jnp.int32),
        jnp.full(4, 50, jnp.int32),
        jnp.ones(4, bool),
    )
    step = build_sharded_step(GRID, mesh, max_handovers_per_shard=8)
    out = sharded_spatial_step(
        step, jnp.asarray(moved), jnp.asarray(prev), jnp.asarray(valid),
        queries, sub_state, 60,
    )

    # Reference: single-device computation.
    new_cells = np.asarray(assign_cells(GRID, jnp.asarray(moved), jnp.asarray(valid)))
    assert np.array_equal(np.asarray(out["cell_of"]), new_cells)
    expected_counts = np.asarray(cell_counts(jnp.asarray(new_cells), GRID.num_cells))
    assert np.array_equal(np.asarray(out["cell_counts"]), expected_counts)

    # Handover rows across shards cover exactly the crossed entities.
    crossed = {i for i in range(n) if prev[i] >= 0 and new_cells[i] >= 0
               and prev[i] != new_cells[i]}
    rows = np.asarray(out["handovers"]).reshape(-1, 3)
    got = {int(r[0]) for r in rows if r[0] >= 0}
    assert got == crossed
    assert int(np.asarray(out["handover_counts"]).sum()) == len(crossed)


def test_slot_reuse_does_not_fabricate_handover():
    """Code-review regression: freed slot's prev cell must not leak."""
    eng = SpatialEngine(GRID, entity_capacity=8, query_capacity=2,
                        sub_capacity=2, max_handovers=8)
    eng.add_entity(1, -100, 0, -100)  # cell 0
    eng.tick(now_ms=0)
    eng.remove_entity(1)
    eng.add_entity(2, 100, 0, 100)  # cell 8, reuses slot of entity 1
    r = eng.tick(now_ms=33)
    assert eng.handover_list(r) == []


def test_first_sighting_seed_enables_first_crossing():
    """Code-review regression: a never-tracked entity's first cross-cell
    move must hand over (prev cell seeded from the old position)."""
    from channeld_tpu.core.settings import global_settings
    from channeld_tpu.spatial.controller import SpatialInfo
    from channeld_tpu.spatial.tpu_controller import TPUSpatialController

    global_settings.tpu_entity_capacity = 16
    global_settings.tpu_query_capacity = 4
    ctl = TPUSpatialController()
    ctl.load_config(dict(
        WorldOffsetX=GRID.offset_x, WorldOffsetZ=GRID.offset_z,
        GridWidth=GRID.cell_w, GridHeight=GRID.cell_h,
        GridCols=GRID.cols, GridRows=GRID.rows,
        ServerCols=1, ServerRows=1, ServerInterestBorderSize=1,
    ))
    eid = 0x80001
    ctl.notify(SpatialInfo(-100, 0, -100), SpatialInfo(100, 0, 100),
               lambda s, d: eid)
    r = ctl.engine.tick(now_ms=0)
    assert ctl.engine.handover_list(r) == [(eid, 0, 8)]


def test_handover_overflow_redetected_next_tick():
    """Code-review regression: crossings beyond max_handovers survive as
    next-tick detections instead of being dropped."""
    eng = SpatialEngine(GRID, entity_capacity=8, query_capacity=2,
                        sub_capacity=2, max_handovers=2)
    for i in range(4):
        eng.add_entity(100 + i, -100, 0, -100)  # all in cell 0
    eng.tick(now_ms=0)
    for i in range(4):
        eng.update_entity(100 + i, 100, 0, 100)  # all cross to cell 8
    r1 = eng.tick(now_ms=33)
    assert int(r1["handover_count"]) == 4
    first = eng.handover_list(r1)
    assert len(first) == 2  # row budget
    r2 = eng.tick(now_ms=66)
    second = eng.handover_list(r2)
    assert len(second) == 2
    assert {e for e, _, _ in first} | {e for e, _, _ in second} == {100, 101, 102, 103}


def test_sharded_step_2d_mesh_matches_single_device():
    """DCN x ICI (hosts, entities) mesh produces identical decisions."""
    from channeld_tpu.parallel.mesh import (
        build_sharded_step,
        make_mesh_2d,
        sharded_spatial_step,
    )

    mesh = make_mesh_2d(2)  # 2 "hosts" x 4 "chips"
    n = 64
    rng = np.random.default_rng(5)
    pts = rng.uniform(-140, 140, size=(n, 3)).astype(np.float32)
    valid = np.ones(n, bool)
    prev = np.asarray(assign_cells(GRID, jnp.asarray(pts), jnp.asarray(valid)))
    moved = pts.copy()
    moved[::7, 2] += 120
    queries = QuerySet(
        kind=jnp.array([AOI_SPHERE], jnp.int32),
        center=jnp.zeros((1, 2), jnp.float32),
        extent=jnp.full((1, 2), 90.0, jnp.float32),
        direction=jnp.ones((1, 2), jnp.float32),
        angle=jnp.zeros(1, jnp.float32),
    )
    sub_state = (jnp.zeros(4, jnp.int32), jnp.full(4, 50, jnp.int32),
                 jnp.ones(4, bool))
    step = build_sharded_step(GRID, mesh, max_handovers_per_shard=8)
    out = sharded_spatial_step(
        step, jnp.asarray(moved), jnp.asarray(prev), jnp.asarray(valid),
        queries, sub_state, 60,
    )
    new_cells = np.asarray(assign_cells(GRID, jnp.asarray(moved), jnp.asarray(valid)))
    assert np.array_equal(np.asarray(out["cell_of"]), new_cells)
    expected_counts = np.asarray(cell_counts(jnp.asarray(new_cells), GRID.num_cells))
    assert np.array_equal(np.asarray(out["cell_counts"]), expected_counts)
    crossed = {i for i in range(n) if prev[i] >= 0 and new_cells[i] >= 0
               and prev[i] != new_cells[i]}
    rows = np.asarray(out["handovers"]).reshape(-1, 3)
    assert {int(r[0]) for r in rows if r[0] >= 0} == crossed


def test_engine_spots_query_matches_host():
    """Device spots AOI (precomputed [Q,C] mask rows) returns the same
    {cell: dist} map as the host path's spots loop (ref: spatial.go spots
    AOI), including per-spot dists, out-of-world skips, and the lazy
    table allocation mid-engine-life."""
    from channeld_tpu.protocol import spatial_pb2

    eng = SpatialEngine(GRID, entity_capacity=16, query_capacity=8,
                        sub_capacity=8, max_handovers=8)
    eng.add_entity(1, 0, 0, 0)
    # A geometric query first: the spots tables must attach lazily later
    # without disturbing existing rows.
    eng.set_query(3, AOI_SPHERE, (0.0, 0.0), (40.0, 0.0))
    r0 = eng.tick(now_ms=0)
    assert eng.interested_cells(r0, 3) == {4: 0}

    # Two spots share cell 5 with different dists: last-wins like the
    # host dict; the exact-boundary spot (x=-50 = a cell edge) pins the
    # divide-then-floor parity; 6th spot is out of world, no dist ->
    # skipped.
    spots = [(-100.0, -100.0), (120.0, 0.0), (130.0, 10.0), (0.0, 120.0),
             (-50.0, 0.0), (999.0, 0.0)]
    dists = [2, 9, 1, 0, 5]
    eng.set_spots_query(9, spots, dists)
    r1 = eng.tick(now_ms=50)

    ctl = host_controller()
    q = spatial_pb2.SpatialInterestQuery()
    for x, z in spots:
        s = q.spotsAOI.spots.add()
        s.x, s.z = x, z
    q.spotsAOI.dists.extend(dists)
    expected = {ch - START: d for ch, d in ctl.query_channel_ids(q).items()}

    assert eng.interested_cells(r1, 9) == expected
    # The earlier geometric query is untouched by the table attach.
    assert eng.interested_cells(r1, 3) == {4: 0}

    # Removing the spots query clears its mask row for slot reuse.
    eng.remove_query(9)
    r2 = eng.tick(now_ms=100)
    assert eng.interested_cells(r2, 9) == {}


def test_sharded_step_spots_queries():
    """Spots tables ride the sharded step as replicated inputs and yield
    the same interest rows as the single-device engine; a spots QuerySet
    against a step compiled without with_spots fails loudly."""
    from channeld_tpu.ops.spatial_ops import AOI_SPOTS
    from channeld_tpu.parallel.mesh import (
        build_sharded_step,
        make_mesh,
        sharded_spatial_step,
    )

    mesh = make_mesh()
    n = 64
    rng = np.random.default_rng(5)
    pts = rng.uniform(-140, 140, size=(n, 3)).astype(np.float32)
    valid = np.ones(n, bool)
    prev = np.asarray(assign_cells(GRID, jnp.asarray(pts), jnp.asarray(valid)))

    spot_dist = np.full((2, GRID.num_cells), -1, np.int32)
    spot_dist[0, [0, 5, 7]] = [2, 1, 0]
    queries = QuerySet(
        kind=jnp.array([AOI_SPOTS, AOI_SPHERE], jnp.int32),
        center=jnp.array([[0, 0], [0, 0]], jnp.float32),
        extent=jnp.array([[0, 0], [40, 0]], jnp.float32),
        direction=jnp.array([[1, 0], [1, 0]], jnp.float32),
        angle=jnp.zeros(2, jnp.float32),
        spot_dist=jnp.asarray(spot_dist),
    )
    sub_state = (
        jnp.zeros(2, jnp.int32),
        jnp.full(2, 50, jnp.int32),
        jnp.ones(2, bool),
    )
    step = build_sharded_step(GRID, mesh, max_handovers_per_shard=16,
                              with_spots=True)
    out = sharded_spatial_step(step, jnp.asarray(pts), jnp.asarray(prev),
                               jnp.asarray(valid), queries, sub_state, 60)
    interest = np.asarray(out["interest"])
    dist = np.asarray(out["dist"])
    assert sorted(np.nonzero(interest[0])[0].tolist()) == [0, 5, 7]
    assert [int(dist[0, c]) for c in (0, 5, 7)] == [2, 1, 0]
    # The geometric query in the same batch is unaffected.
    assert bool(interest[1, 4])

    plain_step = build_sharded_step(GRID, mesh, max_handovers_per_shard=16)
    with pytest.raises(ValueError, match="with_spots"):
        sharded_spatial_step(plain_step, jnp.asarray(pts), jnp.asarray(prev),
                             jnp.asarray(valid), queries, sub_state, 60)


def test_engine_spots_incremental_row_update():
    """Changing one spots row after the tables attach re-uploads only that
    row (device tables updated by scatter) and the tick reflects it."""
    eng = SpatialEngine(GRID, entity_capacity=16, query_capacity=8,
                        sub_capacity=8, max_handovers=8)
    eng.add_entity(1, 0, 0, 0)
    eng.set_spots_query(9, [(-100.0, -100.0)])
    r1 = eng.tick(now_ms=0)
    assert eng.interested_cells(r1, 9) == {0: 0}
    before = eng._d_spot_dist

    eng.set_spots_query(9, [(120.0, 0.0), (0.0, 120.0)], [3, 4])
    assert eng._spot_dirty_rows  # staged, not yet uploaded
    r2 = eng.tick(now_ms=50)
    assert eng.interested_cells(r2, 9) == {5: 3, 7: 4}
    assert not eng._spot_dirty_rows
    # Second query triggers the lazy-attach only once.
    assert eng._d_spot_dist is not before  # scatter produced a new buffer


def test_tpu_profile_trace(tmp_path):
    """-profile tpu writes a jax device trace (xplane + perfetto json)
    viewable in TensorBoard (ref: profiling.go StartProfiling; the tpu
    mode is the device-plane analog of the reference's pprof modes)."""
    import os

    from channeld_tpu.core.profiling import start_profiling, stop_profiling

    start_profiling("tpu", str(tmp_path))
    try:
        eng = SpatialEngine(GRID, entity_capacity=16, query_capacity=8,
                            sub_capacity=8, max_handovers=8)
        eng.add_entity(1, 0, 0, 0)
        eng.tick(now_ms=0)
    finally:
        path = stop_profiling()
    assert path is not None
    found = [f for root, _, files in os.walk(path) for f in files]
    assert any("xplane" in f or "trace" in f for f in found), found


def _drive_engine(eng: SpatialEngine, rng: np.random.Generator) -> list[dict]:
    """Deterministic add/move/remove/query/sub churn; returns tick results."""
    n = 200
    pts = rng.uniform(-140, 140, size=(n, 3)).astype(np.float32)
    for eid in range(n):
        eng.add_entity(1000 + eid, *pts[eid])
    for conn in range(8):
        eng.set_query(conn, [AOI_SPHERE, AOI_BOX, AOI_CONE][conn % 3],
                      tuple(rng.uniform(-100, 100, 2)), (120.0, 80.0),
                      (0.0, 1.0), 0.6)
    eng.set_spots_query(99, [(-100.0, -100.0), (0.0, 0.0)], [2, 0])
    subs = [eng.add_subscription(interval_ms=50 * (1 + s % 3)) for s in range(12)]
    results = []
    for tick, now in enumerate((30, 60, 120)):
        moved = rng.integers(0, n, size=50)
        for eid in moved:
            pts[eid, 0] += rng.uniform(-120, 120)
            pts[eid, 2] += rng.uniform(-120, 120)
            eng.update_entity(1000 + int(eid), *pts[eid])
        if tick == 1:
            eng.remove_entity(1000)
            eng.remove_subscription(subs[0])
            eng.remove_query(2)
        results.append(eng.tick(now_ms=now))
    return results


def test_flush_scatters_compile_nothing_after_warmup():
    """Every dirty count lands in a bucket ``warmup`` compiled: no flush
    scatter compiles inside the guarded tick, where the watchdog reads a
    compile as a hang."""
    from channeld_tpu.ops import engine as engine_mod

    assert [len(engine_mod._bucket(range(k)))
            for k in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert engine_mod._bucket([7, 3, 5]).tolist() == [7, 3, 5, 5]
    assert engine_mod._buckets(48) == [1, 2, 4, 8, 16, 32, 64]

    eng = SpatialEngine(GRID, entity_capacity=48, query_capacity=24,
                        sub_capacity=64, max_handovers=16)
    eng.track_query_changes = True
    eng.warmup()
    compiled = engine_mod._set_rows._cache_size()
    rng = np.random.default_rng(7)
    subs: list[int] = []
    for now, n_dirty in enumerate((1, 3, 5, 11, 23, 48), start=1):
        for s in subs:
            eng.remove_subscription(s)
        subs = [eng.add_subscription(interval_ms=50) for _ in range(n_dirty)]
        for eid in range(n_dirty):
            eng.update_entity(100 + eid, *rng.uniform(-140, 140, 3))
        eng.set_query(500 + now, 1, (0.0, 0.0), (100.0, 0.0))
        if now > 2:
            eng.remove_query(500 + now - 2)  # a freed row resets its baseline
        eng.tick(now_ms=now * 33)
    assert engine_mod._set_rows._cache_size() == compiled


def test_engine_mesh_matches_single_device():
    """The serving engine produces identical gateway-visible decisions with
    the entity arrays sharded over an 8-device mesh vs one device — the
    guarantee that lets TPUSpatialController/the sidecar scale onto a
    slice without behavior drift."""
    from channeld_tpu.parallel.mesh import make_mesh, make_mesh_2d

    for mesh, sharding in ((make_mesh(), "entities"),
                           (make_mesh_2d(2), "entities"),
                           (make_mesh(), "cells")):
        single = SpatialEngine(GRID, entity_capacity=256, query_capacity=128,
                               sub_capacity=64, max_handovers=64)
        meshed = SpatialEngine(GRID, entity_capacity=256, query_capacity=128,
                               sub_capacity=64, max_handovers=64, mesh=mesh,
                               sharding=sharding)
        res_s = _drive_engine(single, np.random.default_rng(42))
        res_m = _drive_engine(meshed, np.random.default_rng(42))
        for tick, (out_s, out_m) in enumerate(zip(res_s, res_m)):
            ctx = f"sharding={sharding} mesh={mesh.shape} tick={tick}"
            if not np.array_equal(np.asarray(out_s["interest"]),
                                  np.asarray(out_m["interest"])):
                # Flake forensics: persist everything needed to replay
                # the divergent element offline (the mismatch has been
                # a 1-element boundary diff; the dump pins which side
                # and which geometry).
                np.savez(
                    "/tmp/mesh_parity_dump.npz",
                    interest_s=np.asarray(out_s["interest"]),
                    interest_m=np.asarray(out_m["interest"]),
                    dist_s=np.asarray(out_s["dist"]),
                    dist_m=np.asarray(out_m["dist"]),
                    q_kind=single._q_kind, q_center=single._q_center,
                    q_extent=single._q_extent, q_dir=single._q_dir,
                    q_angle=single._q_angle,
                    mq_kind=meshed._q_kind, mq_center=meshed._q_center,
                    mq_extent=meshed._q_extent, mq_dir=meshed._q_dir,
                    mq_angle=meshed._q_angle,
                    ctx=np.array(ctx),
                )
            np.testing.assert_array_equal(
                np.asarray(out_s["cell_of"]), np.asarray(out_m["cell_of"]),
                err_msg=ctx)
            np.testing.assert_array_equal(
                np.asarray(out_s["cell_counts"]),
                np.asarray(out_m["cell_counts"]), err_msg=ctx)
            np.testing.assert_array_equal(
                np.asarray(out_s["interest"]), np.asarray(out_m["interest"]),
                err_msg=ctx)
            np.testing.assert_array_equal(
                np.asarray(out_s["due"]), np.asarray(out_m["due"]),
                err_msg=ctx)
            # Handover rows may differ in order (per-shard compaction);
            # compare as sets of (slot, src, dst).
            ho_s = {tuple(r) for r in np.asarray(
                out_s["handovers"][: int(out_s["handover_count"])]) if r[0] >= 0}
            ho_m = {tuple(r) for r in np.asarray(
                out_m["handovers"][: int(out_m["handover_count"])]) if r[0] >= 0}
            assert ho_s == ho_m
        assert single.handover_list(res_s[-1]) is not None
        # Gateway-level accessors agree too.
        assert single.interested_cells(res_s[-1], 0) == \
            meshed.interested_cells(res_m[-1], 0)
        assert single.interested_cells(res_s[-1], 99) == \
            meshed.interested_cells(res_m[-1], 99)


def test_engine_handover_overflow_never_loses_crossings():
    """With a handover budget smaller than one tick's crossings, every
    crossing must still be delivered across subsequent ticks — on the mesh
    path the merged per-shard rows can exceed max_handovers and must all
    be consumed (a clamped row would be committed on device and lost)."""
    from channeld_tpu.parallel.mesh import make_mesh

    for mesh, sharding in ((None, "entities"), (make_mesh(), "entities"),
                           (make_mesh(), "cells")):
        eng = SpatialEngine(GRID, entity_capacity=64, query_capacity=8,
                            sub_capacity=8, max_handovers=10, mesh=mesh,
                            sharding=sharding)
        for eid in range(40):
            eng.add_entity(2000 + eid, -100.0, 0.0, -100.0)  # cell 0
        eng.tick(now_ms=10)
        for eid in range(40):
            eng.update_entity(2000 + eid, 0.0, 0.0, 0.0)  # cell 4
        seen = set()
        for tick in range(12):
            out = eng.tick(now_ms=20 + tick)
            rows = eng.handover_list(out)
            if not rows and len(seen) == 40:
                break
            for entity_id, src, dst in rows:
                assert (src, dst) == (0, 4)
                assert entity_id not in seen, "duplicate handover"
                seen.add(entity_id)
        assert seen == {2000 + eid for eid in range(40)}, (
            f"lost {40 - len(seen)} handovers (mesh={mesh is not None})"
        )
