"""Share of the window's wall time the loop thread spent inside channel
ticks of every type (``channel_tick_duration_sum``, seconds)."""
from benchmark.harness.gateway import total


def read(ctx):
    if not total(ctx["metrics"], "channel_tick_duration_count"):
        return None
    return 100.0 * total(ctx["metrics"],
                         "channel_tick_duration_sum") / ctx["wall_s"]
