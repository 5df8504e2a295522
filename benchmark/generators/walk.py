"""``walk``: every wire entity walks a straight line at ``speed`` from a
seeded start and heading and is reflected at the world's edge.

Parameters of a mix (``benchmark/traffic/<mix>.json``):

``speed``     world units a second
``rate``      updates a second for each entity
``frame_ms``  the servers' send frame; an entity's due times fall on
              frame starts, the entities spread evenly over the frames
              of one period (``1 / rate``)

Where the walks start, where they head and which frame each falls on
are drawn from the run's seed: every seed is another layout. (What holds
the offered load equal from seed to seed is the mix's ``offered`` count,
which ``harness/workers.py:plan`` keeps to.)

The whole schedule is made here, from the seed, before anything is
sent: a position is wherever the walk puts it, so positions fall as
close to a border as ``Grid.border_guard``; one that falls nearer is
carried on along the walk until it clears the border, so that float32
decides every cell the same way on the chip and in the reference.
"""

from __future__ import annotations

import numpy as np


def schedule(grid, n_entities: int, params: dict, seed,
             seconds: float) -> dict:
    """``{"start": f32[N,2], "pos": f32[K,N,2], "due": f64[K,N]}``: where
    each entity is created, where update k of it puts it, and when that
    update falls due, in seconds from the first frame. ``seed`` is what
    ``numpy.random.default_rng`` takes: a whole number or a list of them."""
    rate, speed = float(params["rate"]), float(params["speed"])
    frame = params["frame_ms"] / 1000.0
    period = 1.0 / rate
    frames = max(1, round(period / frame))
    rng = np.random.default_rng(seed)
    n = n_entities
    start = np.stack([rng.uniform(0.0, grid.width, n),
                      rng.uniform(0.0, grid.height, n)], axis=1)
    heading = rng.uniform(0.0, 2 * np.pi, n)
    while True:  # no walk runs along a border: 1 in 8 off either axis
        flat = np.minimum(np.abs(np.cos(heading)), np.abs(np.sin(heading))) < 0.125
        if not flat.any():
            break
        heading[flat] = rng.uniform(0.0, 2 * np.pi, int(flat.sum()))
    step = np.stack([np.cos(heading), np.sin(heading)], axis=1) * speed
    phase = rng.permutation(n) % frames
    k_total = int(np.ceil(seconds * rate)) + 1
    # Update k of an entity is due phase frames into period k + 1, so the
    # first period of the run already carries a full period's updates.
    due = (np.arange(k_total)[:, None] * period + phase[None, :] * frame)
    guard = grid.border_guard
    size = np.array([grid.width, grid.height])
    cell = np.array([grid.cell_w, grid.cell_h])
    offset = np.array([grid.offset_x, grid.offset_z])

    def place(t: np.ndarray) -> np.ndarray:
        """Positions at walk time ``t`` (f64[..., N]) as float32."""
        for _ in range(24):
            raw = start + step * t[..., None]
            folded = np.abs((raw + size) % (2 * size) - size)  # reflect
            p = (folded + offset).astype(np.float32)
            f = (p.astype(np.float64) - offset) % cell
            near = (np.minimum(f, cell - f) < guard).any(axis=-1)
            if not near.any():
                return p
            t = np.where(near, t + 4 * guard / speed, t)
        raise ValueError("walk: could not clear the cell borders")

    return {"start": place(np.zeros(n)), "pos": place(due + period),
            "due": due}
