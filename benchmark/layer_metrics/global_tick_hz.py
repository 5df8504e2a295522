"""GLOBAL ticks that reached the device, a second
(``tick_stage_ms_count{stage="device_step"}``)."""
from benchmark.harness.driver import stage_count


def read(ctx):
    return stage_count(ctx, "device_step") / ctx["wall_s"]
