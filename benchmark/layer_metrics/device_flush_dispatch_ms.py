"""What the device worker spends, for each GLOBAL tick, carrying staged
rows to the device and enqueueing the passes, before it waits for the
chip: the ``step.flush`` and ``step.dispatch`` stages' milliseconds over
the device steps made."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    steps = stage_count(ctx, "device_step")
    if not steps or not stage_count(ctx, "step.dispatch"):
        return None
    return stage_ms(ctx, "step.flush", "step.dispatch") / steps
