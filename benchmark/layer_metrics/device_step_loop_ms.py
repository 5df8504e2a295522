"""The loop thread's milliseconds for one device step: the ``device_step``
stage (from the step's begin to its result in hand, the wait included)
less the ``step.await`` stage (the part of that wait the GLOBAL tick task
gave back to the loop), over the device steps made. Nothing where the
program records no ``step.await``: there the loop thread blocks for the
whole of ``device_step_ms``."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    steps = stage_count(ctx, "device_step")
    if not steps or not stage_count(ctx, "step.await"):
        return None
    return (stage_ms(ctx, "device_step") - stage_ms(ctx, "step.await")) / steps
