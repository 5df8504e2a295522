"""``spatial_step`` (ops/spatial_ops.py): cell of every entity, handover
detection and compaction, per-cell occupancy, interest of every query in
every cell, fan-out due. What the algorithm needs, whatever implements
it: each input read once, each output written once."""

PROGRAM = "jit_spatial_step"


def ops(s: dict) -> float:
    n, q, c, subs = s["entities"], s["queries"], s["cells"], s["subs"]
    # per entity: 2 subtract, 2 divide, 2 floor, index, 2 compares, rank;
    # per (query, cell): 2 gaps, clamp, square-sum-root twice, compare,
    # ceil-divide; per subscription: add and compare.
    return 10.0 * n + 16.0 * q * c + 2.0 * subs


def bytes(s: dict) -> float:  # noqa: A001 (the layout names it)
    n, q, c, subs = s["entities"], s["queries"], s["cells"], s["subs"]
    h = s["max_handovers"]
    read = n * (12 + 4 + 1) + q * 32 + subs * 9
    blob = 4 * (1 + 3 * h + c) + subs // 8
    write = n * (4 + 4) + 12 * h + 4 + 4 * c + q * c * (1 + 4) \
        + subs + subs // 8 + blob + 4 * subs
    return float(read + write)
