"""``sim_step`` (ops/spatial_ops.py): one step of the agent population:
per-cell occupancy and centroid, steering, the four-state machine,
integration. Each input read once, each output written once."""

PROGRAM = "jit_sim_step"


def ops(s: dict) -> float:
    # per slot: cell (6), centroid sums (4), steering (12), three hashed
    # draws (3 x 12), state machine (10), integration and clamp (16).
    return 84.0 * s["entities"] + 3.0 * s["cells"]


def bytes(s: dict) -> float:  # noqa: A001
    n = s["entities"]
    columns = 12 + 12 + 4 + 12  # positions, velocity, state, target
    return float(n * (columns + 1) + s["cells"] + n * columns)
