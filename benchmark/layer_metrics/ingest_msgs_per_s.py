"""Messages the gateway's listeners took in, a second
(``messages_in_total``). The program times ingest only on its deferred
path for user-space forwards (``tick_stage_ms{stage="ingest"}``), which
these cells' messages do not take, so the layer is read as a count."""
from benchmark.harness.gateway import total


def read(ctx):
    count = total(ctx["metrics"], "messages_in_total")
    return count / ctx["wall_s"] if count else None
