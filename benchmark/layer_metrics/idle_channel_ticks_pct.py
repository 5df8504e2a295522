"""Share of the traced window in which the device was idle while the
gateway's loop thread was inside the tick of any channel but GLOBAL
(``channeld/tick.*``)."""
from benchmark.harness.host_spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "channel_ticks_s")
