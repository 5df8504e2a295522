"""What the overload ladder shed, a second (``overload_sheds_total``)."""
from benchmark.harness.gateway import total


def read(ctx):
    return total(ctx["metrics"], "overload_sheds_total") / ctx["wall_s"]
