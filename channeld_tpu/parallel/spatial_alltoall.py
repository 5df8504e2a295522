"""Cell-sharded spatial decision plane: space partitioned over devices.

`parallel/mesh.py` shards the ENTITY axis (every device sees every cell);
this module shards SPACE itself — each device owns a contiguous block of
grid rows, exactly like the reference gives each spatial server an
authority block of cells with a subscribed interest border
(ref: spatial.go:89-124, :481-590). It is the 2D-world instance of the
two standard long-context parallelism patterns:

- **all-to-all redistribution** (the Ulysses/sequence-alltoall shape):
  entities land on whichever shard ingested them; each tick computes
  their cell, packs them into fixed-capacity per-destination buckets,
  and one `all_to_all` over ICI delivers every entity (id + position)
  to the shard that OWNS its cell block. Bucket overflow is never
  silent: the per-entity ``undelivered`` mask identifies exactly which
  ingest-shard slots did not fit, so the caller keeps them queued and
  re-offers them next tick (the same explicit-overflow contract as
  handover compaction).
- **ring halo exchange** (the ring-attention shape): per-cell occupancy
  of the first/last owned grid rows is exchanged with ring neighbors via
  `ppermute`, giving each shard its interest border — the data the
  reference's border subscriptions carry between adjacent servers —
  without any global collective.

Everything is shape-static and jit/shard_map-compatible; tests pin the
sharded results against the dense single-device computation on the
virtual 8-device CPU mesh.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.spatial_ops import (
    GridSpec,
    QuerySet,
    aoi_masks_for_cells,
    assign_cells,
    compact_handovers,
    detect_handovers,
    fanout_due,
)

AXIS = "space"


def make_space_mesh(devices=None) -> Mesh:
    from .mesh import make_mesh

    return make_mesh(devices, axis_name=AXIS)


def rows_per_shard(grid: GridSpec, n_shards: int) -> int:
    if grid.rows % n_shards != 0:
        raise ValueError(
            f"grid rows {grid.rows} must divide evenly over {n_shards} shards"
        )
    return grid.rows // n_shards


def build_cell_sharded_step(grid: GridSpec, mesh: Mesh, bucket: int):
    """Compile the cell-sharded tick.

    Inputs (sharded over AXIS): positions f32[N,3], valid bool[N],
    entity_ids i32[N] — N is the per-ingest-shard capacity x n_shards.

    Returns per-shard (all sharded over AXIS, leading dim = n_shards):
      owned_ids   i32[S, bucket*S]  entity ids now resident on their
                                    owner shard (-1 = empty slot)
      owned_cells i32[S, bucket*S]  the owned entities' global cell ids
      owned_xyz   f32[S, bucket*S, 3]  their positions
      counts      i32[S, rows_blk*cols]   occupancy of the OWNED block
      halo_lo     i32[S, cols]  occupancy of the previous shard's LAST
                                owned row (the south interest border)
      halo_hi     i32[S, cols]  occupancy of the next shard's FIRST
                                owned row (the north interest border)
      undelivered bool[S, n_local]  ingest-shard entity slots whose
                                destination bucket was full this tick;
                                the caller re-offers exactly these
      overflow    i32[S]        sum of undelivered (diagnostic)
    """
    n_shards = mesh.devices.size
    rows_blk = rows_per_shard(grid, n_shards)
    cells_blk = rows_blk * grid.cols

    def shard_fn(positions, valid, entity_ids):
        me = jax.lax.axis_index(AXIS)
        cell_of = assign_cells(grid, positions, valid)  # global cell ids
        row = cell_of // grid.cols
        dest = jnp.where(cell_of >= 0, row // rows_blk, -1)  # owner shard

        # Pack per-destination buckets (fixed shape [n_shards, bucket]).
        # rank within (dest == d) via cumulative counts, like handover
        # compaction; entities beyond a bucket overflow (reported).
        slot_ids = jnp.full((n_shards, bucket), -1, jnp.int32)
        slot_cells = jnp.full((n_shards, bucket), -1, jnp.int32)
        slot_xyz = jnp.zeros((n_shards, bucket, 3), jnp.float32)
        delivered = jnp.zeros_like(dest, dtype=bool)
        for d in range(n_shards):  # static, small (n_shards <= 16)
            mask = dest == d
            rank = jnp.cumsum(mask, dtype=jnp.int32) - 1
            fits = mask & (rank < bucket)
            delivered = delivered | fits
            (idx,) = jnp.nonzero(mask, size=bucket, fill_value=0)
            idx = idx.astype(jnp.int32)
            row_valid = jnp.arange(bucket) < jnp.sum(fits, dtype=jnp.int32)
            slot_ids = slot_ids.at[d].set(
                jnp.where(row_valid, entity_ids[idx], -1))
            slot_cells = slot_cells.at[d].set(
                jnp.where(row_valid, cell_of[idx], -1))
            slot_xyz = slot_xyz.at[d].set(
                jnp.where(row_valid[:, None], positions[idx], 0.0))
        undelivered = (dest >= 0) & ~delivered
        overflow = jnp.sum(undelivered, dtype=jnp.int32)

        # The Ulysses move: [n_shards, bucket] -> every shard receives its
        # own-destination bucket from every source.
        recv_ids = jax.lax.all_to_all(slot_ids, AXIS, 0, 0, tiled=False)
        recv_cells = jax.lax.all_to_all(slot_cells, AXIS, 0, 0, tiled=False)
        recv_xyz = jax.lax.all_to_all(slot_xyz, AXIS, 0, 0, tiled=False)
        owned_ids = recv_ids.reshape(-1)  # [n_shards * bucket]
        owned_cells = recv_cells.reshape(-1)
        owned_xyz = recv_xyz.reshape(-1, 3)

        # Owned-block occupancy: local cell index = global - block start.
        block_start = me * cells_blk
        local = jnp.where(owned_cells >= 0, owned_cells - block_start, 0)
        present = owned_cells >= 0
        counts = jnp.zeros(cells_blk, jnp.int32).at[local].add(
            present.astype(jnp.int32))

        # Ring halo: the interest border. ppermute moves each shard's last
        # owned row north (to me+1) and first owned row south (to me-1) —
        # one neighbor hop over ICI, never a global collective.
        last_row = counts[-grid.cols:]
        first_row = counts[: grid.cols]
        halo_lo = jax.lax.ppermute(  # from me-1's last row
            last_row, AXIS,
            [(i, (i + 1) % n_shards) for i in range(n_shards)])
        halo_hi = jax.lax.ppermute(  # from me+1's first row
            first_row, AXIS,
            [(i, (i - 1) % n_shards) for i in range(n_shards)])
        # World edges have no neighbor: zero the wrapped halos.
        halo_lo = jnp.where(me == 0, jnp.zeros_like(halo_lo), halo_lo)
        halo_hi = jnp.where(me == n_shards - 1, jnp.zeros_like(halo_hi),
                            halo_hi)
        return (owned_ids[None], owned_cells[None], owned_xyz[None],
                counts[None], halo_lo[None], halo_hi[None],
                undelivered[None], overflow[None])

    sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS),) * 8,
        check_vma=False,
    )
    return jax.jit(sharded)


def cells_per_shard(grid: GridSpec, n_shards: int) -> int:
    """Owned-block size for the serving step: contiguous cell ranges,
    padded so any grid divides over any shard count (cell range ==
    row block whenever rows % n_shards == 0)."""
    return -(-grid.num_cells // n_shards)


def build_cell_serving_step(grid: GridSpec, mesh: Mesh, bucket: int,
                            max_handovers_per_shard: int,
                            with_spots: bool = False):
    """The cell-sharded plane as a SERVING backend: same result contract
    as parallel.mesh.build_sharded_step (the engine normalizes either
    into one tick result), but space itself is partitioned —

    - each shard OWNS a contiguous block of ``cells_per_shard`` cells
      (the reference's per-server authority block, spatial.go:89-124);
    - per-tick entity (id, cell) pairs are bucket-packed per owner and
      delivered with ONE all_to_all over ICI; the owner accumulates its
      block's occupancy from what it received — never a global
      collective over the entity axis;
    - the [Q, C] AOI interest/dist planes are computed column-block-wise
      (each shard only its own cells via aoi_masks_for_cells) and
      all_gathered — the per-device AOI work scales 1/n_shards with
      world size, the axis on which worlds actually grow;
    - bucket overflow is never silent: ``undelivered`` (slot-sharded
      bool[N]) marks exactly the entities whose owner bucket was full —
      they stay in the ingest arrays and are re-offered next tick
      (redistribution is stateless per tick), and their occupancy is
      missing from this tick's counts until delivered. ``overflow``
      carries the per-shard sums for the controller's shed metric.

    Handover detection/compaction and the fan-out due scan are
    slot-local / replicated exactly as in the entity-sharded step —
    they don't depend on cell ownership.

    Inputs: entity arrays slot-sharded over the mesh's (single) axis;
    queries + sub state replicated. ``bucket`` = per-(source, dest)
    capacity of the redistribution; n_local (= N / n_shards) makes
    delivery exact.
    """
    if len(mesh.axis_names) != 1:
        raise ValueError(
            "cells sharding partitions space over one axis; got mesh axes "
            f"{mesh.axis_names} — use a 1D mesh (make_mesh)"
        )
    axis = mesh.axis_names[0]
    n_shards = int(mesh.devices.size)
    cells_blk = cells_per_shard(grid, n_shards)

    def shard_fn(positions, prev_cell, valid, q_kind, q_center, q_extent,
                 q_dir, q_angle, *rest):
        if with_spots:
            spot_dist, last_ms, interval_ms, active, now_ms = rest
        else:
            spot_dist = None
            last_ms, interval_ms, active, now_ms = rest
        queries = QuerySet(q_kind, q_center, q_extent, q_dir, q_angle,
                           spot_dist)
        me = jax.lax.axis_index(axis)
        cell_of = assign_cells(grid, positions, valid)

        # Handover plane: slot-local, identical to the entity-sharded step.
        handover_mask = detect_handovers(prev_cell, cell_of)
        ho_count, ho_rows, reported = compact_handovers(
            handover_mask, prev_cell, cell_of, max_handovers_per_shard
        )
        committed_prev = jnp.where(
            handover_mask & ~reported, prev_cell, cell_of)
        shard_size = positions.shape[0]
        offset = (me * shard_size).astype(jnp.int32)
        ho_rows = ho_rows.at[:, 0].set(
            jnp.where(ho_rows[:, 0] >= 0, ho_rows[:, 0] + offset, -1))
        all_counts = jax.lax.all_gather(ho_count, axis)
        all_rows = jax.lax.all_gather(ho_rows, axis)

        # Redistribution: deliver (global slot, cell) to the cell's owner.
        dest = jnp.where(cell_of >= 0, cell_of // cells_blk, -1)
        slot_ids = jnp.full((n_shards, bucket), -1, jnp.int32)
        slot_cells = jnp.full((n_shards, bucket), -1, jnp.int32)
        delivered = jnp.zeros_like(dest, dtype=bool)
        global_slots = offset + jnp.arange(shard_size, dtype=jnp.int32)
        for d in range(n_shards):  # static, small
            mask = dest == d
            rank = jnp.cumsum(mask, dtype=jnp.int32) - 1
            fits = mask & (rank < bucket)
            delivered = delivered | fits
            (idx,) = jnp.nonzero(mask, size=bucket, fill_value=0)
            idx = idx.astype(jnp.int32)
            row_valid = jnp.arange(bucket) < jnp.sum(fits, dtype=jnp.int32)
            slot_ids = slot_ids.at[d].set(
                jnp.where(row_valid, global_slots[idx], -1))
            slot_cells = slot_cells.at[d].set(
                jnp.where(row_valid, cell_of[idx], -1))
        undelivered = (dest >= 0) & ~delivered
        overflow = jnp.sum(undelivered, dtype=jnp.int32)
        recv_ids = jax.lax.all_to_all(slot_ids, axis, 0, 0, tiled=False)
        recv_cells = jax.lax.all_to_all(slot_cells, axis, 0, 0, tiled=False)
        owned_ids = recv_ids.reshape(-1)          # [n_shards * bucket]
        owned_cells = recv_cells.reshape(-1)

        # Owned-block occupancy from what the owner received.
        block_start = me * cells_blk
        local = jnp.where(owned_cells >= 0, owned_cells - block_start, 0)
        present = owned_cells >= 0
        blk_counts = jnp.zeros(cells_blk, jnp.int32).at[local].add(
            present.astype(jnp.int32))
        counts = jax.lax.all_gather(blk_counts, axis)  # [S, cells_blk]
        # (No ring-halo exchange here: nothing in the serving path consumes
        # it, and a row-width halo is only geometric on row-aligned blocks
        # — the ingest-plane step, build_cell_sharded_step, carries the
        # tested halo exchange for consumers that want borders.)

        # Column-block AOI: only my cells' columns, gathered to [Q, C_pad].
        blk_ids = block_start + jnp.arange(cells_blk, dtype=jnp.int32)
        spot_slice = None
        if spot_dist is not None:
            # The table arrives pre-padded to cells_blk * n_shards columns
            # (see cell_serving_spatial_step) so the last shard's slice
            # never clamps — a clamped start would misalign spot columns
            # against blk_ids and silently drop border-cell interest.
            spot_slice = jax.lax.dynamic_slice_in_dim(
                spot_dist, block_start, cells_blk, axis=1)
        blk_hit, blk_dist = aoi_masks_for_cells(
            grid, queries, blk_ids, spot_slice)
        interest = jax.lax.all_gather(blk_hit, axis, axis=1)   # [Q,S,blk]
        dist = jax.lax.all_gather(blk_dist, axis, axis=1)

        # Fan-out due: replicated, computed once per shard.
        due, new_last = fanout_due(now_ms, last_ms, interval_ms, active)
        return (cell_of, committed_prev, all_counts, all_rows, counts,
                interest, dist, due, new_last, undelivered,
                overflow[None], owned_ids[None])

    sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(axis), P(axis), P(axis),
            P(), P(), P(), P(), P(),
            *((P(),) if with_spots else ()),
            P(), P(), P(),
            P(),
        ),
        out_specs=(
            P(axis), P(axis),      # cell_of, committed_prev
            P(), P(),              # handover counts/rows (gathered)
            P(), P(), P(),         # counts, interest, dist (gathered)
            P(), P(),              # due, new_last (replicated)
            P(axis),               # undelivered (slot-sharded)
            P(axis), P(axis),      # overflow, owned_ids
        ),
        check_vma=False,
    )

    def full(*args):
        (cell_of, committed_prev, all_counts, all_rows, counts, interest,
         dist, due, new_last, undelivered, overflow,
         owned_ids) = sharded(*args)
        c = grid.num_cells
        counts = counts.reshape(-1)[:c]
        interest = interest.reshape(interest.shape[0], -1)[:, :c]
        dist = dist.reshape(dist.shape[0], -1)[:, :c]
        due_packed = jnp.packbits(due)
        return (cell_of, committed_prev, all_counts, all_rows, counts,
                interest, dist, due, due_packed, new_last, undelivered,
                overflow, owned_ids)

    jitted = jax.jit(full, donate_argnums=(1,))

    def step(*args):
        return jitted(*args)

    step.with_spots = with_spots
    step.bucket = bucket
    step.cells_blk = cells_blk
    step.n_shards = n_shards
    return step


def cell_serving_spatial_step(step_fn, positions, prev_cell, valid,
                              queries: QuerySet, sub_state, now_ms):
    """Drive a build_cell_serving_step function; returns the engine's
    normalized tick-result dict (parallel.mesh.sharded_spatial_step's
    contract plus the cells-plane extras)."""
    last_ms, interval_ms, active = sub_state
    if queries.spot_dist is not None and not step_fn.with_spots:
        raise ValueError(
            "queries carry a spots table; build_cell_serving_step("
            "with_spots=True)")
    if queries.spot_dist is None and step_fn.with_spots:
        raise ValueError(
            "step compiled with_spots=True but queries have no spots table")
    spot_args = ()
    if step_fn.with_spots:
        # Pad to the sharded cell space (cells_blk * n_shards columns, -1 =
        # no interest) so every shard's block slice is in-bounds.
        c_pad = step_fn.cells_blk * step_fn.n_shards
        spot = queries.spot_dist
        if spot.shape[1] < c_pad:
            spot = jnp.pad(spot, ((0, 0), (0, c_pad - spot.shape[1])),
                           constant_values=-1)
        spot_args = (spot,)
    (cell_of, committed_prev, ho_counts, ho_rows, counts, interest, dist,
     due, due_packed, new_last, undelivered, overflow,
     owned_ids) = step_fn(
        positions, prev_cell, valid,
        queries.kind, queries.center, queries.extent, queries.direction,
        queries.angle, *spot_args, last_ms, interval_ms, active,
        jnp.int32(now_ms),
    )
    return {
        "cell_of": cell_of,
        "committed_prev": committed_prev,
        "handover_counts": ho_counts,
        "handovers": ho_rows,
        "cell_counts": counts,
        "interest": interest,
        "dist": dist,
        "due": due,
        "due_packed": due_packed,
        "new_last_fanout_ms": new_last,
        "undelivered": undelivered,
        "overflow": overflow,
        "owned_ids": owned_ids,
    }
