"""Batched spatial decision kernels (JAX).

The TPU-native replacement for the reference's per-entity/per-subscriber
CPU loops (ref: pkg/channeld/spatial.go:169-317 cell math + AOI sampling,
data.go:175-291 fan-out due scan, spatial.go:612-626 handover detection).
Everything here is shape-static, branch-free, and jit-compatible: state
lives in fixed-capacity slot arrays with validity masks, and each tick
recomputes assignment / interest / due decisions for *all* entities,
queries, and subscriptions at once.

Semantics notes vs the host path:
- Cell assignment matches exactly: floor((p - offset) / cell), id =
  start + x + z*cols, invalid (<0) outside the world.
- AOI interest is computed as exact shape-vs-cell-rectangle overlap
  instead of the host's half-grid-step point sampling — a strict
  superset of the sampled cells for the same shape, with the same
  ceil(dist / cell-diagonal) distance metric.
- The fan-out due decision reproduces the (last, last+interval] window
  advance: a due subscriber's window moves forward one interval.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class GridSpec(NamedTuple):
    """Static grid geometry, baked into the compiled step."""

    offset_x: float
    offset_z: float
    cell_w: float
    cell_h: float
    cols: int
    rows: int

    @property
    def num_cells(self) -> int:
        return self.cols * self.rows

    @property
    def diagonal(self) -> float:
        return float((self.cell_w**2 + self.cell_h**2) ** 0.5)


# ---- cell assignment ------------------------------------------------------


def assign_cells(grid: GridSpec, positions: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """positions f32[N,3] -> cell index i32[N]; -1 for invalid/outside.

    (ref: spatial.go:169-180 GetChannelIdWithOffset, vectorized.)
    """
    gx = jnp.floor((positions[:, 0] - grid.offset_x) / grid.cell_w).astype(jnp.int32)
    gz = jnp.floor((positions[:, 2] - grid.offset_z) / grid.cell_h).astype(jnp.int32)
    inside = (gx >= 0) & (gx < grid.cols) & (gz >= 0) & (gz < grid.rows) & valid
    return jnp.where(inside, gx + gz * grid.cols, -1)


# ---- handover detection ---------------------------------------------------


def detect_handovers(old_cell: jnp.ndarray, new_cell: jnp.ndarray) -> jnp.ndarray:
    """bool[N]: entity crossed a cell boundary this tick
    (ref: spatial.go:613-626 src != dst check, batched)."""
    return (old_cell >= 0) & (new_cell >= 0) & (old_cell != new_cell)


def compact_handovers(
    handover_mask: jnp.ndarray,
    old_cell: jnp.ndarray,
    new_cell: jnp.ndarray,
    max_out: int,
):
    """Pack (entity_slot, src_cell, dst_cell) rows for up to ``max_out``
    crossings into a fixed-shape output (count, rows i32[max_out,3]).

    Fixed shapes keep the step recompile-free; overflow beyond max_out is
    reported via count so the host can fall back next tick.
    """
    n = handover_mask.shape[0]
    max_out = min(max_out, n)
    count = jnp.sum(handover_mask, dtype=jnp.int32)
    # Ordinal of each crossing among all crossings (slot order) — an O(N)
    # scan instead of an O(N log N) sort.
    rank = jnp.cumsum(handover_mask, dtype=jnp.int32) - 1
    reported = handover_mask & (rank < max_out)
    # First max_out crossing slots, in slot order: scatter each reported
    # slot's index into its rank. This reuses the cumsum above, which
    # the jnp.nonzero(size=...) compaction it replaced computed again;
    # the ledger's breakdown.device_ops has what the fusion costs on
    # the chip. Unreported slots write into a discard lane.
    slot = jnp.where(reported, rank, max_out)
    idx = (
        jnp.zeros(max_out + 1, jnp.int32)
        .at[slot]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")[:max_out]
    )
    rows = jnp.stack([idx, old_cell[idx], new_cell[idx]], axis=1)
    row_valid = jnp.arange(max_out) < jnp.minimum(count, max_out)
    rows = jnp.where(row_valid[:, None], rows, -1)
    return count, rows, reported


# ---- per-cell occupancy ---------------------------------------------------


def cell_counts(cell_of: jnp.ndarray, num_cells: int) -> jnp.ndarray:
    """Entity count per cell, i32[num_cells] (segment-sum)."""
    valid = cell_of >= 0
    return jnp.zeros(num_cells, jnp.int32).at[
        jnp.where(valid, cell_of, 0)
    ].add(valid.astype(jnp.int32))


# ---- AOI: query x cell interest masks ------------------------------------

AOI_NONE = 0
AOI_SPHERE = 1
AOI_BOX = 2
AOI_CONE = 3
AOI_SPOTS = 4


class QuerySet(NamedTuple):
    """SoA batch of client interest queries (ref: channeld.proto
    SpatialInterestQuery; one active shape per query).

    Spots queries don't reduce to a geometric test, so they ride as a
    precomputed per-query cell table (rasterized host-side when the query
    is set — spots change rarely, cells are few): one i32[Q,C] damping
    distance with -1 meaning "no interest" (the mask is ``dist >= 0``).
    The field stays ``None`` until the first spots query, keeping the
    common-case compiled step free of the table.
    """

    kind: jnp.ndarray  # i32[Q] in {NONE, SPHERE, BOX, CONE, SPOTS}
    center: jnp.ndarray  # f32[Q,2] (x,z)
    extent: jnp.ndarray  # f32[Q,2] box half-extent (x,z); radius in [:,0] for sphere/cone
    direction: jnp.ndarray  # f32[Q,2] cone direction (x,z), normalized
    angle: jnp.ndarray  # f32[Q] cone half-angle, radians
    spot_dist: Optional[jnp.ndarray] = None  # i32[Q,C]; -1 = no interest


def aoi_masks(grid: GridSpec, queries: QuerySet):
    """Interest of every query in every cell.

    Returns (interest bool[Q,C], dist i32[Q,C]) where dist is the
    ceil(center-to-sample / cell-diagonal) damping distance, matching the
    host path's metric (ref: spatial.go:182-317). One source of truth:
    the full-grid case of aoi_masks_for_cells (the cell-sharded plane
    calls it per block)."""
    return aoi_masks_for_cells(
        grid, queries, jnp.arange(grid.num_cells, dtype=jnp.int32),
        queries.spot_dist,
    )


def aoi_masks_for_cells(grid: GridSpec, queries: QuerySet, cell_ids,
                        spot_dist_slice=None):
    """``aoi_masks`` for an arbitrary i32[Cb] vector of GLOBAL cell ids —
    the cell-sharded plane computes only its owned block's columns and
    all_gathers the rest (parallel/spatial_alltoall.py). ``cell_ids`` may
    be traced (block starts depend on axis_index). Ids outside
    [0, num_cells) are padding: never interested. ``spot_dist_slice`` is
    the [Q, Cb] slice of the spots table for these cells (None = no spots
    queries registered). Parity with aoi_masks is pinned by
    tests/test_spatial_alltoall.py."""
    col = (cell_ids % grid.cols).astype(jnp.float32)
    row = (cell_ids // grid.cols).astype(jnp.float32)
    centers = jnp.stack(
        [grid.offset_x + (col + 0.5) * grid.cell_w,
         grid.offset_z + (row + 0.5) * grid.cell_h], axis=1)  # [Cb,2]
    cell_valid = (cell_ids >= 0) & (cell_ids < grid.num_cells)
    half = jnp.array([grid.cell_w * 0.5, grid.cell_h * 0.5])

    delta = jnp.abs(queries.center[:, None, :] - centers[None, :, :])
    gap = jnp.maximum(delta - half[None, None, :], 0.0)
    rect_dist = jnp.sqrt(jnp.sum(gap * gap, axis=-1))
    center_dist = jnp.sqrt(
        jnp.sum((queries.center[:, None, :] - centers) ** 2, axis=-1))

    radius = queries.extent[:, 0:1]
    sphere_hit = rect_dist <= radius
    box_hit = jnp.all(
        delta <= (queries.extent[:, None, :] + half[None, None, :]), axis=-1)
    to_cell = centers[None, :, :] - queries.center[:, None, :]
    to_len = jnp.maximum(jnp.sqrt(jnp.sum(to_cell * to_cell, axis=-1)), 1e-9)
    cosine = jnp.sum(to_cell * queries.direction[:, None, :], axis=-1) / to_len
    in_angle = cosine >= jnp.cos(queries.angle)[:, None]
    apex_cell = rect_dist <= 0.0
    cone_hit = (rect_dist <= radius) & (in_angle | apex_cell)

    hit = (
        ((queries.kind[:, None] == AOI_SPHERE) & sphere_hit)
        | ((queries.kind[:, None] == AOI_BOX) & box_hit)
        | ((queries.kind[:, None] == AOI_CONE) & cone_hit)
    ) & cell_valid[None, :]
    dist = jnp.ceil(center_dist / grid.diagonal).astype(jnp.int32)
    dist = jnp.where(rect_dist <= 0.0, 0, dist)
    if spot_dist_slice is None:
        return hit, dist
    is_spots = queries.kind[:, None] == AOI_SPOTS
    spots_hit = (spot_dist_slice >= 0) & cell_valid[None, :]
    hit = jnp.where(is_spots, spots_hit, hit)
    dist = jnp.where(is_spots & spots_hit, spot_dist_slice, dist)
    return hit, dist


def apply_spots_overlay(hit, dist, queries: QuerySet):
    """Overlay spots queries' host-rasterized table onto geometric
    interest/dist planes (ref: spatial.go spots loop — each spot's cell
    with its per-spot dist, default 0; -1 = cell not targeted). Shared by
    the XLA and Mosaic AOI paths so spots semantics can never diverge."""
    if queries.spot_dist is None:
        return hit, dist
    is_spots = queries.kind[:, None] == AOI_SPOTS
    spots_hit = queries.spot_dist >= 0
    hit = jnp.where(is_spots, spots_hit, hit)
    dist = jnp.where(is_spots & spots_hit, queries.spot_dist, dist)
    return hit, dist


def damping_intervals_ms(
    dist: jnp.ndarray,
    interest: jnp.ndarray,
    tiers: jnp.ndarray,
    tier_intervals: jnp.ndarray,
    default_interval: int,
) -> jnp.ndarray:
    """Map grid distance -> fan-out interval per (query, cell)
    (ref: message_spatial.go:10-38 damping table).

    ``tiers`` i32[T] ascending max-distances, ``tier_intervals`` i32[T].
    Beyond the last tier the default interval applies.
    """
    # Index of the first tier whose max_distance >= dist.
    idx = jnp.searchsorted(tiers, dist.ravel(), side="left").reshape(dist.shape)
    in_table = idx < tiers.shape[0]
    interval = jnp.where(
        in_table, tier_intervals[jnp.minimum(idx, tiers.shape[0] - 1)], default_interval
    )
    return jnp.where(interest, interval, 0)


# ---- fan-out due decision -------------------------------------------------


def fanout_due(
    now_ms: jnp.ndarray,
    last_fanout_ms: jnp.ndarray,
    interval_ms: jnp.ndarray,
    active: jnp.ndarray,
):
    """Which subscriptions are due, and their advanced window starts.

    Times are int32 milliseconds since engine start (int64 is emulated on
    TPU; i32 ms wraps after ~24 days, far beyond a session). Reproduces
    tick_data's window advance (ref: data.go:252-258): a due sub's
    last-fan-out moves to last+interval (not to ``now``), keeping late
    updates deliverable. Returns (due bool[S], new_last i32[S]).
    """
    next_ms = last_fanout_ms + interval_ms
    due = active & (now_ms >= next_ms)
    return due, jnp.where(due, next_ms, last_fanout_ms)


# ---- the fused per-tick step ---------------------------------------------


@partial(jax.jit, static_argnums=(0, 6, 8), donate_argnums=(2,))
def spatial_step(
    grid: GridSpec,
    positions: jnp.ndarray,  # f32[N,3]
    prev_cell: jnp.ndarray,  # i32[N] (donated; replaced by new assignment)
    valid: jnp.ndarray,  # bool[N]
    queries: QuerySet,
    sub_state: tuple,  # (last_fanout_ms i32[S], interval_ms i32[S], active bool[S])
    max_handovers: int,
    now_ms,
    use_pallas: bool = False,
):
    """One decision tick, fully on device: cell assignment + handover
    detection/compaction + per-cell occupancy + AOI interest + fan-out
    due mask. Returns everything the host needs to route messages.

    ``use_pallas`` swaps the assignment+occupancy pass for the fused
    Mosaic kernel (TPU backends only; ~1.7x for that pass)."""
    if use_pallas:
        from .pallas_kernels import aoi_masks_pallas, assign_and_count_pallas

        cell_of, counts = assign_and_count_pallas(grid, positions, valid)
    else:
        cell_of = assign_cells(grid, positions, valid)
        counts = cell_counts(cell_of, grid.num_cells)
    handover_mask = detect_handovers(prev_cell, cell_of)
    ho_count, ho_rows, reported = compact_handovers(
        handover_mask, prev_cell, cell_of, max_handovers
    )
    # Crossings that overflowed the row budget keep their *old* cell as the
    # next tick's baseline, so they are re-detected instead of lost.
    committed_prev = jnp.where(handover_mask & ~reported, prev_cell, cell_of)
    if use_pallas:
        interest, dist = aoi_masks_pallas(grid, queries)
    else:
        interest, dist = aoi_masks(grid, queries)
    last_ms, interval_ms, active = sub_state
    due, new_last = fanout_due(now_ms, last_ms, interval_ms, active)
    due_packed = jnp.packbits(due)
    # Single host-consumption blob: one D2H transfer per tick instead of
    # one per output (each transfer costs a dispatch + possibly a full
    # transport round trip). Layout (i32):
    #   [0]                count
    #   [1 : 1+3K]         handover rows, row-major
    #   [... : +C]         cell counts
    #   [... : +ceil(S/32)] due bitmask words (u8-packed, zero-padded)
    pad = (-due_packed.shape[0]) % 4
    due_words = jax.lax.bitcast_convert_type(
        jnp.pad(due_packed, (0, pad)).reshape(-1, 4), jnp.int32
    ).reshape(-1)
    consume = jnp.concatenate([
        ho_count[None], ho_rows.reshape(-1), counts, due_words
    ])
    return {
        "cell_of": cell_of,
        "committed_prev": committed_prev,
        "handover_count": ho_count,
        "handovers": ho_rows,
        "cell_counts": counts,
        "interest": interest,
        "dist": dist,
        "due": due,
        # Bit-packed due mask: 8x less D2H for the per-tick host readback
        # (unpack host-side with np.unpackbits).
        "due_packed": due_packed,
        "consume": consume,
        "new_last_fanout_ms": new_last,
    }


def parse_consume_blob(blob, max_handovers: int, num_cells: int, num_subs: int):
    """Host-side split of the packed consumption blob (numpy)."""
    import numpy as np

    blob = np.asarray(blob)
    count = int(blob[0])
    rows_end = 1 + 3 * max_handovers
    rows = blob[1:rows_end].reshape(max_handovers, 3)
    counts = blob[rows_end : rows_end + num_cells]
    due_words = blob[rows_end + num_cells :]
    due = np.unpackbits(due_words.view(np.uint8))[:num_subs]
    return count, rows, counts, due


# ---- standing-query diff / compaction (doc/query_engine.md) ---------------


@partial(jax.jit, static_argnums=(4,))
def diff_query_masks(
    prev_interest: jnp.ndarray,  # bool[Q,C] committed baseline
    prev_dist: jnp.ndarray,  # i32[Q,C]
    interest: jnp.ndarray,  # bool[Q,C] this tick's masks
    dist: jnp.ndarray,  # i32[Q,C]
    max_rows: int,
):
    """Diff this tick's query-interest masks against the committed
    baseline ON DEVICE and compact the delta to ``(query, cell, dist)``
    rows — the standing-query plane's entire per-tick host protocol.

    A (q, c) entry is *changed* when interest flipped either way, or when
    it stayed interested but the damping distance moved (the host must
    re-subscribe with refreshed fan-out options, mirroring
    apply_interest_diff's always-refresh semantics). Rows carry the NEW
    dist; ``dist == -1`` means interest was removed. Compaction reuses the
    cumsum-rank scatter of compact_handovers over the flattened [Q*C]
    plane. Changes beyond ``max_rows`` keep their *previous* baseline
    value so they re-diff next tick instead of being lost (same overflow
    contract as handovers); ``count`` reports the true total so the host
    can see the backlog.

    Returns (blob i32[1+3*max_rows], next_interest bool[Q,C],
    next_dist i32[Q,C]) where blob = [count][rows row-major] is the ONE
    device->host transfer the plane is allowed per tick, and next_* is
    the baseline to commit for the following tick.
    """
    q, c = interest.shape
    max_rows = min(max_rows, q * c)
    changed = (interest != prev_interest) | (interest & (dist != prev_dist))
    flat = changed.reshape(-1)
    n = flat.shape[0]
    count = jnp.sum(flat, dtype=jnp.int32)
    rank = jnp.cumsum(flat, dtype=jnp.int32) - 1
    reported = flat & (rank < max_rows)
    slot = jnp.where(reported, rank, max_rows)
    idx = (
        jnp.zeros(max_rows + 1, jnp.int32)
        .at[slot]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")[:max_rows]
    )
    new_dist = jnp.where(interest.reshape(-1)[idx], dist.reshape(-1)[idx], -1)
    rows = jnp.stack([idx // c, idx % c, new_dist], axis=1)
    row_valid = jnp.arange(max_rows) < jnp.minimum(count, max_rows)
    rows = jnp.where(row_valid[:, None], rows, -1)
    keep_prev = (changed & ~reported.reshape(q, c))
    next_interest = jnp.where(keep_prev, prev_interest, interest)
    next_dist = jnp.where(keep_prev, prev_dist, dist)
    blob = jnp.concatenate([count[None], rows.reshape(-1)])
    return blob, next_interest, next_dist


def parse_query_blob(blob):
    """Host-side split of the standing-query changed-rows blob (numpy):
    (total_changed, rows i32[R,3]) where R is the blob's own row budget
    (diff_query_masks clamps the configured max to Q*C, so the effective
    budget is read from the blob, never assumed); rows beyond
    min(total, R) are -1 padding."""
    import numpy as np

    blob = np.asarray(blob)
    return int(blob[0]), blob[1:].reshape(-1, 3)


# ---- simulation plane: agent steering + behavior FSM (doc/simulation.md) --

SIM_IDLE = 0
SIM_WANDER = 1
SIM_SEEK = 2
SIM_FLEE = 3


class SimParams(NamedTuple):
    """Static steering/FSM constants, baked into the compiled sim step
    (changing a knob recompiles once; see the ``sim_*`` knob table in
    doc/simulation.md)."""

    dt: float  # integration step, seconds of world time per tick
    max_speed: float  # clamp on |v|, world units / s
    accel: float  # max steering acceleration, world units / s^2
    separation: float  # crowded-cell push weight
    cohesion: float  # sparse-cell centroid pull weight
    arrive_radius: float  # waypoint reached within this xz distance
    crowd: int  # cell occupancy above which separation wins
    p_wander: float  # per-tick idle -> wander probability
    p_seek: float  # per-tick wander -> seek probability
    p_idle: float  # per-tick wander -> idle probability


def sim_rand_u32(seed, tick, lane: int, n: int) -> jnp.ndarray:
    """Counter-based RNG: u32[n] hash of (seed, tick, lane, slot).

    Stateless and replayable — the same (seed, tick) always produces the
    same draws regardless of history, so a WAL-replayed or guard-rebuilt
    population resumes the exact trajectory it would have taken (the
    replayability contract in doc/simulation.md). A Weyl-sequence input
    through the murmur3 fmix32 finalizer; no key threading, no state
    array to rebuild.
    """
    idx = jnp.arange(n, dtype=jnp.uint32)
    x = idx * jnp.uint32(0x9E3779B9)
    x = x + jnp.asarray(seed, jnp.uint32)
    x = x ^ (jnp.asarray(tick, jnp.uint32) * jnp.uint32(0x85EBCA6B))
    x = x + jnp.uint32(lane) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _unit_f32(bits: jnp.ndarray) -> jnp.ndarray:
    """u32 -> f32 uniform in [0, 1) (top 24 bits, exact in f32)."""
    return (bits >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


@partial(jax.jit, static_argnums=(0, 7), donate_argnums=(1, 2, 3, 4))
def sim_step(
    grid: GridSpec,
    positions: jnp.ndarray,  # f32[N,3] (donated; replaced by integration)
    vel: jnp.ndarray,  # f32[N,3] (donated)
    state: jnp.ndarray,  # i32[N] FSM state (donated)
    target: jnp.ndarray,  # f32[N,3] current waypoint (donated)
    agent: jnp.ndarray,  # bool[N] slot hosts a simulated agent
    flee_cells: jnp.ndarray,  # bool[C] danger mask (query-plane sensor hits)
    params: SimParams,
    seed,  # u32 scalar (traced: changing the seed never recompiles)
    tick,  # i32 scalar (traced)
):
    """One population step, fully on device: flocking steering from
    per-cell occupancy aggregates, waypoint seeking, and the 4-state
    behavior FSM — all branch-free over the SAME entity arrays the
    spatial pass reads, so the new positions feed straight into cell
    assignment with zero extra transfers.

    Flocking is the per-cell reduction of boids: separation pushes out of
    crowded cells and cohesion pulls strays toward their cell centroid,
    computed with O(N) segment-sums instead of O(N^2) pairwise distances
    (the aggregate form is what makes 100K agents a sub-millisecond MXU
    pass). FLEE is driven by the standing-query plane: ``flee_cells`` is
    the host-rasterized micro-cell mask of sensor hits, uploaded only
    when a sensor's interest set changes — never per tick.

    Non-agent lanes (humans, free slots) pass through every output
    unchanged. Returns (positions, vel, state, target).
    """
    n = positions.shape[0]
    cell_of = assign_cells(grid, positions, agent)
    in_world = cell_of >= 0
    safe_cell = jnp.where(in_world, cell_of, 0)
    live = agent & in_world

    # Per-cell occupancy + centroid of the agent population (xz plane).
    w = live.astype(jnp.float32)
    counts = jnp.zeros(grid.num_cells, jnp.float32).at[safe_cell].add(w)
    xz = positions[:, (0, 2)]
    sums = jnp.zeros((grid.num_cells, 2), jnp.float32).at[safe_cell].add(
        xz * w[:, None]
    )
    centroid = sums / jnp.maximum(counts, 1.0)[:, None]
    my_count = counts[safe_cell]
    away = xz - centroid[safe_cell]
    away_len = jnp.sqrt(jnp.sum(away * away, axis=-1, keepdims=True))
    away_dir = away / jnp.maximum(away_len, 1e-6)
    crowded = (my_count > params.crowd)[:, None]
    steer_xz = jnp.where(
        crowded,
        away_dir * params.separation,
        -away_dir * jnp.minimum(away_len, 1.0) * params.cohesion,
    )

    # FSM transitions (doc/simulation.md state diagram). One dice lane
    # per decision keeps draws independent across lanes and ticks.
    r_trans = _unit_f32(sim_rand_u32(seed, tick, 0, n))
    to_t = target - positions
    dist_t = jnp.sqrt(to_t[:, 0] ** 2 + to_t[:, 2] ** 2)
    arrived = dist_t <= params.arrive_radius
    in_danger = live & flee_cells[safe_cell]

    st = state
    new_st = jnp.where(st == SIM_SEEK, jnp.where(arrived, SIM_IDLE, st), st)
    is_wander = st == SIM_WANDER
    new_st = jnp.where(is_wander & (r_trans < params.p_seek), SIM_SEEK, new_st)
    new_st = jnp.where(
        is_wander
        & (r_trans >= params.p_seek)
        & (r_trans < params.p_seek + params.p_idle),
        SIM_IDLE,
        new_st,
    )
    new_st = jnp.where(
        (st == SIM_IDLE) & (r_trans < params.p_wander), SIM_WANDER, new_st
    )
    # Sensor hits override everything; an escaped fleer calms to WANDER.
    new_st = jnp.where(
        in_danger, SIM_FLEE, jnp.where((st == SIM_FLEE) & ~in_danger, SIM_WANDER, new_st)
    )

    # Waypoints: a fresh SEEK draws a world-uniform target; FLEE aims at
    # the reflection of the danger cell's center through the agent (run
    # straight away from the hit cell).
    r_tx = _unit_f32(sim_rand_u32(seed, tick, 1, n))
    r_tz = _unit_f32(sim_rand_u32(seed, tick, 2, n))
    rand_target = jnp.stack(
        [
            grid.offset_x + r_tx * (grid.cols * grid.cell_w),
            positions[:, 1],
            grid.offset_z + r_tz * (grid.rows * grid.cell_h),
        ],
        axis=1,
    )
    cell_cx = grid.offset_x + (
        (safe_cell % grid.cols).astype(jnp.float32) + 0.5
    ) * grid.cell_w
    cell_cz = grid.offset_z + (
        (safe_cell // grid.cols).astype(jnp.float32) + 0.5
    ) * grid.cell_h
    flee_target = jnp.stack(
        [
            positions[:, 0] * 2.0 - cell_cx,
            positions[:, 1],
            positions[:, 2] * 2.0 - cell_cz,
        ],
        axis=1,
    )
    entered_seek = (new_st == SIM_SEEK) & (st != SIM_SEEK)
    entered_flee = (new_st == SIM_FLEE) & (st != SIM_FLEE)
    new_target = jnp.where(entered_seek[:, None], rand_target, target)
    new_target = jnp.where(entered_flee[:, None], flee_target, new_target)

    # Desired velocity by state (xz plane; y is carried, never integrated).
    to_nt = new_target - positions
    nt_len = jnp.sqrt(to_nt[:, 0] ** 2 + to_nt[:, 2] ** 2)
    goal_dir = to_nt / jnp.maximum(nt_len, 1e-6)[:, None]
    r_jx = _unit_f32(sim_rand_u32(seed, tick, 3, n)) * 2.0 - 1.0
    r_jz = _unit_f32(sim_rand_u32(seed, tick, 4, n)) * 2.0 - 1.0
    jitter = jnp.stack([r_jx, jnp.zeros(n, jnp.float32), r_jz], axis=1)
    seeking = (new_st == SIM_SEEK) | (new_st == SIM_FLEE)
    desired = jnp.where(
        seeking[:, None],
        goal_dir * params.max_speed,
        jnp.where(
            (new_st == SIM_WANDER)[:, None],
            vel * 0.9 + jitter * params.max_speed * 0.5,
            jnp.zeros_like(vel),
        ),
    )
    desired = desired.at[:, 0].add(steer_xz[:, 0] * params.max_speed)
    desired = desired.at[:, 2].add(steer_xz[:, 1] * params.max_speed)

    # Accelerate toward desired, clamp speed, integrate, clamp into the
    # world (a clamped agent stays assignable — it can never escape the
    # grid and vanish from the spatial pass).
    dv = desired - vel
    dv_len = jnp.sqrt(jnp.sum(dv * dv, axis=-1, keepdims=True))
    step = jnp.minimum(dv_len, params.accel * params.dt)
    new_vel = vel + dv / jnp.maximum(dv_len, 1e-6) * step
    speed = jnp.sqrt(jnp.sum(new_vel * new_vel, axis=-1, keepdims=True))
    new_vel = new_vel * jnp.minimum(
        jnp.float32(1.0), params.max_speed / jnp.maximum(speed, 1e-6)
    )
    new_vel = new_vel.at[:, 1].set(0.0)
    new_pos = positions + new_vel * params.dt
    margin = jnp.float32(min(grid.cell_w, grid.cell_h) * 1e-3)
    new_pos = new_pos.at[:, 0].set(
        jnp.clip(
            new_pos[:, 0],
            grid.offset_x + margin,
            grid.offset_x + grid.cols * grid.cell_w - margin,
        )
    )
    new_pos = new_pos.at[:, 2].set(
        jnp.clip(
            new_pos[:, 2],
            grid.offset_z + margin,
            grid.offset_z + grid.rows * grid.cell_h - margin,
        )
    )

    lane = agent[:, None]
    return (
        jnp.where(lane, new_pos, positions),
        jnp.where(lane, new_vel, vel),
        jnp.where(agent, new_st, state),
        jnp.where(lane, new_target, target),
    )
