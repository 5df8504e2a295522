"""``sim_step``'s share of its roofline in the traced window."""
from benchmark.harness.driver import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "sim_step")
