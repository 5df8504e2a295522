"""What one census holds the GLOBAL tick for: the transfer of its
columns (``step.census_fetch``) and their absorb, journal and commit
(``sim_census``), over the censuses of the window."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    censuses = stage_count(ctx, "sim_census")
    if not censuses:
        return None
    return stage_ms(ctx, "step.census_fetch", "sim_census") / censuses
