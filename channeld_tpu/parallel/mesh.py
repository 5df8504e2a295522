"""Multi-chip sharding of the spatial decision step.

The reference scales by giving each spatial *server* a block of grid
cells plus an interest border (ref: spatial.go:387-590) — model-parallel
over space. On a TPU mesh the analogous scale-out is simpler and better
balanced: shard the entity slot arrays over the mesh's data axis, keep
the (small) query set and grid geometry replicated, and combine per-cell
aggregates with ``psum`` over ICI. Cell occupancy plays the role of the
halo: every device learns the global per-cell counts in one collective
instead of exchanging border entities.

All sharding is expressed with jax.sharding.Mesh + shard_map so the same
code runs on one chip (mesh of 1), a v5e-4 slice, or a multi-host mesh
over DCN.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.spatial_ops import (
    GridSpec,
    QuerySet,
    aoi_masks,
    assign_cells,
    cell_counts,
    compact_handovers,
    detect_handovers,
    fanout_due,
)

DATA_AXIS = "entities"
HOST_AXIS = "hosts"


def make_mesh(devices: Optional[list] = None,
              axis_name: str = DATA_AXIS) -> Mesh:
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices, dtype=object).reshape(-1), (axis_name,))


def make_mesh_2d(n_hosts: int, devices: Optional[list] = None) -> Mesh:
    """Multi-host mesh: a (hosts, entities) grid where the host axis rides
    DCN and the entity axis rides ICI. Entity arrays shard over BOTH axes
    (each host's chips own a contiguous slot range); the occupancy psum
    reduces over ('hosts', 'entities'), so XLA emits the ICI all-reduce
    within each host and the DCN all-reduce across hosts — the same
    hierarchy the reference gets from spatial servers + gateway fan-in."""
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    arr = np.array(devices, dtype=object).reshape(n_hosts, -1)
    return Mesh(arr, (HOST_AXIS, DATA_AXIS))


def mesh_from_config(n_devices: int, n_hosts: int = 1) -> Optional[Mesh]:
    """Mesh for the serving engine from config/flag values; None when
    n_devices is 0 (single-device step)."""
    if not n_devices:
        return None
    devices = jax.devices()
    if len(devices) < n_devices:
        raise ValueError(
            f"mesh wants {n_devices} devices but only {len(devices)} present"
        )
    devices = devices[:n_devices]
    if n_hosts > 1:
        return make_mesh_2d(n_hosts, devices)
    return make_mesh(devices)


def entity_sharding(mesh: Mesh) -> NamedSharding:
    """Joint sharding over every mesh axis — matches build_sharded_step's
    entity spec for both 1D and 2D meshes."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def build_sharded_step(grid: GridSpec, mesh: Mesh, max_handovers_per_shard: int,
                       with_spots: bool = False):
    """Compile the per-tick decision step sharded over ``mesh``.

    Entity arrays (positions/prev_cell/valid) are sharded on the mesh's
    data axes (single-axis ICI mesh from ``make_mesh``, or the
    (hosts, entities) DCN x ICI mesh from ``make_mesh_2d``); queries and
    subscription state are replicated; outputs: cell_of sharded, handover
    rows per-shard (gathered), cell counts and AOI masks replicated.

    ``with_spots=True`` adds the replicated [Q,C] spots dist table to
    the signature (see QuerySet.spot_dist) — build with it when any
    query uses SpotsAOI.
    """
    axes = tuple(mesh.axis_names)  # ("entities",) or ("hosts", "entities")
    entity_spec = P(axes)  # shard jointly over every mesh axis

    def shard_fn(positions, prev_cell, valid, q_kind, q_center, q_extent,
                 q_dir, q_angle, *rest):
        if with_spots:
            spot_dist, last_ms, interval_ms, active, now_ms = rest
        else:
            spot_dist = None
            last_ms, interval_ms, active, now_ms = rest
        queries = QuerySet(q_kind, q_center, q_extent, q_dir, q_angle,
                           spot_dist)
        cell_of = assign_cells(grid, positions, valid)
        handover_mask = detect_handovers(prev_cell, cell_of)
        ho_count, ho_rows, reported = compact_handovers(
            handover_mask, prev_cell, cell_of, max_handovers_per_shard
        )
        # Crossings that overflowed this shard's row budget keep their old
        # cell as next tick's baseline so they are re-detected, not lost —
        # the same overflow contract as the single-device spatial_step.
        committed_prev = jnp.where(handover_mask & ~reported, prev_cell, cell_of)
        # Local slot indices -> global entity slots (row-major shard order).
        shard_index = jnp.int32(0)
        for axis in axes:
            shard_index = shard_index * jax.lax.axis_size(axis) + jax.lax.axis_index(axis)
        shard_size = positions.shape[0]
        offset = (shard_index * shard_size).astype(jnp.int32)
        ho_rows = ho_rows.at[:, 0].set(
            jnp.where(ho_rows[:, 0] >= 0, ho_rows[:, 0] + offset, -1)
        )
        # Global per-cell occupancy: reduces over ICI within a host and
        # DCN across hosts — the collective that replaces the reference's
        # cross-server interest border.
        counts = jax.lax.psum(cell_counts(cell_of, grid.num_cells), axes)
        # Replicated decisions computed once per shard (identical inputs).
        interest, dist = aoi_masks(grid, queries)
        due, new_last = fanout_due(now_ms, last_ms, interval_ms, active)
        # Gather every shard's handover rows so the host reads one array.
        all_counts = jax.lax.all_gather(ho_count, axes)
        all_rows = jax.lax.all_gather(ho_rows, axes)
        return (cell_of, committed_prev, all_counts, all_rows, counts,
                interest, dist, due, new_last)

    sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            entity_spec, entity_spec, entity_spec,  # positions, prev_cell, valid
            P(), P(), P(), P(), P(),  # query SoA (replicated)
            *((P(),) if with_spots else ()),  # spots dist table (replicated)
            P(), P(), P(),  # sub state (replicated)
            P(),  # now_ms
        ),
        out_specs=(
            entity_spec, entity_spec,  # cell_of, committed_prev
            P(), P(),  # handover counts/rows (gathered, replicated)
            P(), P(), P(), P(), P(),
        ),
        check_vma=False,
    )

    def full(*args):
        (cell_of, committed_prev, all_counts, all_rows, counts, interest,
         dist, due, new_last) = sharded(*args)
        # Bit-packed due mask: same D2H-thrift trick as spatial_step.
        due_packed = jnp.packbits(due)
        return (cell_of, committed_prev, all_counts, all_rows, counts,
                interest, dist, due, due_packed, new_last)

    jitted = jax.jit(full, donate_argnums=(1,))

    def step(*args):
        return jitted(*args)

    step.with_spots = with_spots
    return step


def sharded_spatial_step(step_fn, positions, prev_cell, valid, queries: QuerySet,
                         sub_state, now_ms):
    last_ms, interval_ms, active = sub_state
    if queries.spot_dist is not None and not getattr(step_fn, "with_spots", False):
        raise ValueError(
            "queries carry a spots table; build_sharded_step(with_spots=True)"
        )
    if queries.spot_dist is None and getattr(step_fn, "with_spots", False):
        raise ValueError(
            "step compiled with_spots=True but queries have no spots table"
        )
    spot_args = (
        (queries.spot_dist,) if getattr(step_fn, "with_spots", False) else ()
    )
    (cell_of, committed_prev, ho_counts, ho_rows, counts, interest, dist,
     due, due_packed, new_last) = step_fn(
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
    }


def merge_handover_shards(ho_counts, ho_rows) -> "tuple[int, object]":
    """Flatten per-shard gathered handover rows into one (count, rows[K,3])
    array in shard order, dropping unused row slots. Host-side numpy."""
    import numpy as np

    counts = np.asarray(ho_counts).reshape(-1)
    rows = np.asarray(ho_rows)
    rows = rows.reshape(counts.shape[0], -1, 3)
    per_shard = rows.shape[1]
    merged = [rows[i, : min(int(counts[i]), per_shard)] for i in range(len(counts))]
    flat = (np.concatenate(merged, axis=0) if merged
            else np.zeros((0, 3), np.int32))
    return int(flat.shape[0]), flat
