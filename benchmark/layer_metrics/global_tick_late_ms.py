"""How long after it was due (the tick before it plus its interval) a
GLOBAL tick started, mean over the window (``tick_late_ms{channel_type=
"GLOBAL"}``, counted in the program's tick loop)."""
from benchmark.harness.gateway import total


def read(ctx):
    ticks = total(ctx["metrics"], "tick_late_ms_count", channel_type="GLOBAL")
    if not ticks:
        return None
    return total(ctx["metrics"], "tick_late_ms_sum",
                 channel_type="GLOBAL") / ticks
