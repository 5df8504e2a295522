"""Pallas TPU kernels for the spatial hot path.

The fused XLA step (spatial_ops.spatial_step) is already dispatch-bound
at bench sizes, but the two memory-heaviest pieces — cell assignment and
the per-cell occupancy histogram — stream the whole entity table through
the VPU. This kernel fuses them into one VMEM pass: each grid step loads
a tile of positions, computes cell indices, and accumulates the one-hot
histogram in place, so positions are read exactly once and the [N, C]
one-hot never materializes in HBM.

``spatial_step(use_pallas=True)`` calls these on TPU backends
(``pallas_available``); elsewhere it runs the XLA implementation, and
tests run the kernels in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .spatial_ops import GridSpec

# Entities per grid step = SUBLANES x LANES. The kernel's rank-3 one-hot is
# nominally (8, 256, c_pad) i32 = 8 KiB x c_pad: 2 MiB at the benchmark
# world's 225 cells (c_pad 256), past the 16 MiB scoped-VMEM default from
# c_pad 2,048. Mosaic does not materialise it: on jax 0.9.0 / libtpu 0.0.34
# (v5e, PR 21's chip run) both kernels compiled and matched XLA at every
# c_pad tried from 256 to 14,464 (14,400 cells, the partition plane's
# micro grid at depth 3), with 131,072 slots and 4,096 query rows. No c_pad
# was refused; if a later compiler does materialise it, that is where to look.
TILE = 2048
SUBLANES = 8
LANES = TILE // SUBLANES


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _assign_count_kernel(grid: GridSpec, c_pad: int, x_ref, z_ref, valid_ref,
                         cell_ref, counts_ref):
    from jax.experimental import pallas as pl

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...]  # (SUBLANES, LANES)
    z = z_ref[...]
    gx = jnp.floor((x - grid.offset_x) / grid.cell_w).astype(jnp.int32)
    gz = jnp.floor((z - grid.offset_z) / grid.cell_h).astype(jnp.int32)
    # valid arrives as i32: a bool (i8-stored) input would need an i8->i1
    # vector truncation Mosaic can't lower on v5e.
    inside = (
        (gx >= 0) & (gx < grid.cols) & (gz >= 0) & (gz < grid.rows)
        & (valid_ref[...] != 0)
    )
    cell = jnp.where(inside, gx + gz * grid.cols, -1)
    cell_ref[...] = cell

    # One-hot accumulate entirely in VMEM: rank-3 broadcast compare (no
    # reshapes — Mosaic can't re-tile (8,256)->(2048,1)) reduced over the
    # lane-block axis into per-sublane partial histograms.
    cell_ids = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES, c_pad), 2)
    onehot = (cell[:, :, None] == cell_ids).astype(jnp.int32)
    counts_ref[...] += jnp.sum(onehot, axis=1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def assign_and_count_pallas(grid: GridSpec, positions, valid,
                            interpret: bool = False):
    """Fused cell assignment + occupancy histogram.

    positions f32[N,3], valid bool[N] -> (cell_of i32[N], counts i32[C]).
    N is padded to a TILE multiple internally; C to a lane multiple.
    """
    from jax.experimental import pallas as pl

    n = positions.shape[0]
    n_pad = _cdiv(n, TILE) * TILE
    c = grid.num_cells
    c_pad = _cdiv(c, 128) * 128

    x = jnp.pad(positions[:, 0], (0, n_pad - n), constant_values=jnp.inf)
    z = jnp.pad(positions[:, 2], (0, n_pad - n), constant_values=jnp.inf)
    v = jnp.pad(valid.astype(jnp.int32), (0, n_pad - n), constant_values=0)
    tiles = n_pad // TILE
    shape = (tiles * SUBLANES, LANES)

    cell, counts = pl.pallas_call(
        functools.partial(_assign_count_kernel, grid, c_pad),
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, c_pad), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(shape, jnp.int32),
            jax.ShapeDtypeStruct((SUBLANES, c_pad), jnp.int32),
        ],
        interpret=interpret,
    )(x.reshape(shape), z.reshape(shape), v.reshape(shape))
    return cell.reshape(n_pad)[:n], jnp.sum(counts, axis=0)[:c]


SUB_Q = 8  # queries per grid step (sublane dimension)


def _aoi_kernel(grid: GridSpec, c_pad: int, kind_ref, cx_ref, cz_ref,
                ex_ref, ez_ref, dx_ref, dz_ref, ang_ref, hit_ref, dist_ref):
    """One tile: SUB_Q queries x all (padded) cells. Cell geometry is
    generated in-register from iota — nothing but the query SoA tile is
    read, and the [Q,C] interest/dist planes are written exactly once."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (SUB_Q, c_pad), 1)
    col = (ids % grid.cols).astype(jnp.float32)
    row = (ids // grid.cols).astype(jnp.float32)
    ccx = grid.offset_x + (col + 0.5) * grid.cell_w
    ccz = grid.offset_z + (row + 0.5) * grid.cell_h
    cell_valid = ids < grid.num_cells  # lane padding never hits

    kind = kind_ref[...]  # (SUB_Q, 1) broadcasts along lanes
    qx, qz = cx_ref[...], cz_ref[...]
    ex, ez = ex_ref[...], ez_ref[...]

    dx = jnp.abs(qx - ccx)
    dz = jnp.abs(qz - ccz)
    half_w = grid.cell_w * 0.5
    half_h = grid.cell_h * 0.5
    gap_x = jnp.maximum(dx - half_w, 0.0)
    gap_z = jnp.maximum(dz - half_h, 0.0)
    rect_dist = jnp.sqrt(gap_x * gap_x + gap_z * gap_z)
    center_dist = jnp.sqrt((qx - ccx) ** 2 + (qz - ccz) ** 2)

    radius = ex
    sphere_hit = rect_dist <= radius
    box_hit = (dx <= ex + half_w) & (dz <= ez + half_h)
    to_x = ccx - qx
    to_z = ccz - qz
    to_len = jnp.maximum(jnp.sqrt(to_x * to_x + to_z * to_z), 1e-9)
    cosine = (to_x * dx_ref[...] + to_z * dz_ref[...]) / to_len
    in_angle = cosine >= jnp.cos(ang_ref[...])
    apex_cell = rect_dist <= 0.0
    cone_hit = (rect_dist <= radius) & (in_angle | apex_cell)

    from .spatial_ops import AOI_BOX, AOI_CONE, AOI_SPHERE

    # Pure i1 mask algebra: a where-chain with a Python bool arm lowers to
    # an i8 constant vector + i8->i1 truncation Mosaic can't compile.
    hit = (
        ((kind == AOI_SPHERE) & sphere_hit)
        | ((kind == AOI_BOX) & box_hit)
        | ((kind == AOI_CONE) & cone_hit)
    ) & cell_valid
    dist = jnp.ceil(center_dist / grid.diagonal).astype(jnp.int32)
    dist = jnp.where(rect_dist <= 0.0, 0, dist)
    hit_ref[...] = hit.astype(jnp.int32)
    dist_ref[...] = dist


@functools.partial(jax.jit, static_argnums=(0, 2))
def _aoi_masks_pallas_geom(grid: GridSpec, q_soa, interpret: bool = False):
    """Geometric AOI pass on device: (hit i32[Q,C_pad], dist i32[Q,C_pad])."""
    from jax.experimental import pallas as pl

    kind, center, extent, direction, angle = q_soa
    q = kind.shape[0]
    q_pad = _cdiv(q, SUB_Q) * SUB_Q
    c_pad = _cdiv(grid.num_cells, 128) * 128

    def col2d(arr, fill=0):
        return jnp.pad(arr, (0, q_pad - q), constant_values=fill)[:, None]

    cols = [
        col2d(kind.astype(jnp.int32)),
        col2d(center[:, 0]), col2d(center[:, 1]),
        col2d(extent[:, 0]), col2d(extent[:, 1]),
        col2d(direction[:, 0]), col2d(direction[:, 1]),
        col2d(angle),
    ]
    tiles = q_pad // SUB_Q
    hit, dist = pl.pallas_call(
        functools.partial(_aoi_kernel, grid, c_pad),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((SUB_Q, 1), lambda i: (i, 0))] * len(cols),
        out_specs=[
            pl.BlockSpec((SUB_Q, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((SUB_Q, c_pad), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, c_pad), jnp.int32),
            jax.ShapeDtypeStruct((q_pad, c_pad), jnp.int32),
        ],
        interpret=interpret,
    )(*cols)
    return hit[:q, : grid.num_cells], dist[:q, : grid.num_cells]


def aoi_masks_pallas(grid: GridSpec, queries, interpret: bool = False):
    """Mosaic-fused replacement for spatial_ops.aoi_masks: same results
    (interest bool[Q,C], dist i32[Q,C]); the spots-table overlay stays in
    XLA (it is a gather, not geometry)."""
    hit, dist = _aoi_masks_pallas_geom(
        grid,
        (queries.kind, queries.center, queries.extent, queries.direction,
         queries.angle),
        interpret,
    )
    from .spatial_ops import apply_spots_overlay

    return apply_spots_overlay(hit.astype(bool), dist, queries)


def pallas_available() -> bool:
    """True when the default backend compiles Mosaic kernels."""
    return jax.default_backend() == "tpu"
