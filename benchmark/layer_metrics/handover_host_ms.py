"""Host orchestration of handovers for each GLOBAL tick: the
``handover`` stage's milliseconds over the device steps made."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    steps = stage_count(ctx, "device_step")
    return stage_ms(ctx, "handover") / steps if steps else None
