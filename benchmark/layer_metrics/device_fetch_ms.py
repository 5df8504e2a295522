"""What the device worker spends, for each GLOBAL tick, blocked on the
chip and on the per-tick readback: the ``step.fetch`` stage's
milliseconds over the device steps made."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    steps = stage_count(ctx, "device_step")
    if not steps or not stage_count(ctx, "step.fetch"):
        return None
    return stage_ms(ctx, "step.fetch") / steps
