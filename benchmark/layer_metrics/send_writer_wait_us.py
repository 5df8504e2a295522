"""Microseconds from a hand-off to the send writer thread to that
batch's first byte written, the queue delay the writer adds to a packet:
Δ``send_writer_wait_seconds_total`` over Δ``send_writer_batches_total``.
Nothing where the program does not count them (no writer), or no batch
went out."""
from benchmark.harness.gateway import total


def read(ctx):
    m = ctx["metrics"]
    batches = total(m, "send_writer_batches_total")
    if not batches:
        return None
    return 1e6 * total(m, "send_writer_wait_seconds_total") / batches
