"""How stale the rows of the cells' own tables are when a client's socket
reads them: read time minus the row's due time, median over every
stamped row that any client read through a SPATIAL channel in the
window. This is the spatial fan-out path alone (``fanout_due`` on the
device, the cell channel's tick on the host); the end-to-end deliveries
take the first read by any path."""
from benchmark.harness.stats import percentile


def read(ctx):
    rows = ctx["cell_rows_ms"]
    return percentile(rows, 50) if len(rows) else None
