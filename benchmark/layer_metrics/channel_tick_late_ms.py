"""How long after it was due a channel tick of any type but GLOBAL
started, mean over every such tick of the window (``tick_late_ms``)."""
from benchmark.harness.gateway import total


def read(ctx):
    m = ctx["metrics"]
    ticks = (total(m, "tick_late_ms_count")
             - total(m, "tick_late_ms_count", channel_type="GLOBAL"))
    if not ticks:
        return None
    return (total(m, "tick_late_ms_sum")
            - total(m, "tick_late_ms_sum", channel_type="GLOBAL")) / ticks
