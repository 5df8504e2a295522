"""Share of the window's wall time the shared send pump spent flushing
connections: the ``send_pump`` stage, one observation a pass that
flushed at least one, from its first flush to its last. Nothing where
the program records no such stage."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    if not stage_count(ctx, "send_pump"):
        return None
    return 100.0 * stage_ms(ctx, "send_pump") / (ctx["wall_s"] * 1000.0)
