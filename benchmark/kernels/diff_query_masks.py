"""``diff_query_masks`` (ops/spatial_ops.py): this tick's interest masks
against the committed baseline, compacted to changed rows. Each input
read once, each output written once."""

PROGRAM = "jit_diff_query_masks"


def ops(s: dict) -> float:
    # per (query, cell): two compares, and, or, rank add.
    return 5.0 * s["queries"] * s["cells"]


def bytes(s: dict) -> float:  # noqa: A001
    qc = s["queries"] * s["cells"]
    rows = min(s["query_rows_max"], qc)
    return float(2 * qc * (1 + 4) + qc * (1 + 4) + 4 * (1 + 3 * rows))
