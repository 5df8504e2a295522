"""Carrying the device's due decisions to the cell channels, for each
GLOBAL tick: the ``publish_due`` stage's milliseconds over the device
steps made."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    steps = stage_count(ctx, "device_step")
    if not steps or not stage_count(ctx, "publish_due"):
        return None
    return stage_ms(ctx, "publish_due") / steps
