"""Share of the window's wall time spent in the ``messages`` and
``fanout`` stages of the channel ticks (``tick_stage_ms_sum``)."""
from benchmark.harness.driver import stage_ms


def read(ctx):
    return 100.0 * stage_ms(ctx, "messages", "fanout") / (ctx["wall_s"] * 1000.0)
