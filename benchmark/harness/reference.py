"""The plain reference: grid arithmetic on the configuration's numbers.

Cell of a point, owner block of a cell, circle against rectangle. It
imports nothing of the program (``spatial/grid.py`` may change under a
later PR; this may not) and computes in float64 on the float32 values
the wire carries, which is what the configuration's float32 decides
wherever a point is more than a few float32 steps from a border; the
generators keep every point at least ``border_guard`` away from one.
"""

from __future__ import annotations

import json
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Grid:
    offset_x: float
    offset_z: float
    cell_w: float
    cell_h: float
    cols: int
    rows: int
    server_cols: int
    server_rows: int
    cell_start: int  # channel id of cell 0
    entity_start: int  # entity channel ids lie above this

    @classmethod
    def load(cls, scc_path: str, cell_start: int = 0x10000,
             entity_start: int = 0x80000) -> "Grid":
        with open(scc_path) as f:
            c = json.load(f)["Config"]
        return cls(float(c["WorldOffsetX"]), float(c["WorldOffsetZ"]),
                   float(c["GridWidth"]), float(c["GridHeight"]),
                   int(c["GridCols"]), int(c["GridRows"]),
                   int(c["ServerCols"]), int(c["ServerRows"]),
                   cell_start, entity_start)

    @property
    def num_cells(self) -> int:
        return self.cols * self.rows

    @property
    def num_servers(self) -> int:
        return self.server_cols * self.server_rows

    @property
    def width(self) -> float:
        return self.cell_w * self.cols

    @property
    def height(self) -> float:
        return self.cell_h * self.rows

    @property
    def border_guard(self) -> float:
        """64 float32 steps at the world's far corner: nearer to a border
        than this, float32 arithmetic may fairly put a point on either
        side, so no traffic is placed there."""
        reach = max(abs(self.offset_x), abs(self.offset_x + self.width),
                    abs(self.offset_z), abs(self.offset_z + self.height))
        return 64.0 * float(np.spacing(np.float32(reach)))

    def cells_of(self, x, z) -> np.ndarray:
        """Cell index (0-based, row-major) of each point; -1 outside."""
        gx = np.floor((np.asarray(x, np.float64) - self.offset_x) / self.cell_w)
        gz = np.floor((np.asarray(z, np.float64) - self.offset_z) / self.cell_h)
        inside = (gx >= 0) & (gx < self.cols) & (gz >= 0) & (gz < self.rows)
        return np.where(inside, gx + gz * self.cols, -1).astype(np.int64)

    def border_distance(self, x, z) -> np.ndarray:
        """Distance of each point to the nearest cell border line."""
        fx = (np.asarray(x, np.float64) - self.offset_x) % self.cell_w
        fz = (np.asarray(z, np.float64) - self.offset_z) % self.cell_h
        return np.minimum(np.minimum(fx, self.cell_w - fx),
                          np.minimum(fz, self.cell_h - fz))

    def server_of_cell(self, cell: int) -> int:
        """Index of the server whose block holds ``cell``: the world is
        cut into server_cols x server_rows equal blocks, row-major."""
        col, row = cell % self.cols, cell // self.cols
        return (col // (self.cols // self.server_cols)
                + (row // (self.rows // self.server_rows)) * self.server_cols)

    def cell_rect(self, cell: int) -> tuple[float, float, float, float]:
        x0 = self.offset_x + (cell % self.cols) * self.cell_w
        z0 = self.offset_z + (cell // self.cols) * self.cell_h
        return x0, z0, x0 + self.cell_w, z0 + self.cell_h

    def sphere_cells(self, cx: float, cz: float,
                     radius: float) -> tuple[frozenset, float]:
        """Cells whose rectangle a circle overlaps, and how near the
        closest rectangle, hit or missed, comes to the circle's edge."""
        cells = np.arange(self.num_cells)
        x0 = self.offset_x + (cells % self.cols) * self.cell_w
        z0 = self.offset_z + (cells // self.cols) * self.cell_h
        d = np.hypot(
            np.maximum(np.maximum(x0 - cx, cx - (x0 + self.cell_w)), 0.0),
            np.maximum(np.maximum(z0 - cz, cz - (z0 + self.cell_h)), 0.0))
        return (frozenset(np.nonzero(d <= radius)[0].tolist()),
                float(np.abs(d - radius).min()))


def f32(x) -> float:
    """The value a proto ``float`` field carries for ``x``."""
    return float(np.float32(x))
