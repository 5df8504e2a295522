"""How long an entity that crossed a border waits for its new owner: 95th
percentile, over every crossing of the window, of the time from the due
time of the update that carried the entity across to the destination
owner's socket reading its ``CHANNEL_DATA_HANDOVER``; one never read
stands at the end of the drain. Taken at the peers' sockets. It is a
per-layer metric because its runs spread too widely to hold a bound
(PERF.md section 2)."""
from benchmark.harness.stats import percentile


def read(ctx):
    waits = ctx["handover_ms"]
    return percentile(waits, 95) if len(waits) else None
