"""Share of the traced window in which the device was idle while the
gateway's loop thread was inside no channel's tick: socket callbacks,
scheduling, sleep."""
from benchmark.harness.host_spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "unspanned_s")
