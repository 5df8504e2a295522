"""Mean of the ``device_step`` stage: flush, the dispatches, the blocking
wait and the readback of one GLOBAL tick, by the host's clock."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    steps = stage_count(ctx, "device_step")
    return stage_ms(ctx, "device_step") / steps if steps else None
