"""Share of the window's wall time spent taking messages in: each read's
inline decode and enqueue (``ingest_inline``), the deferred runs'
dispatch (``ingest``) and the retries of stashed messages
(``stash_retry``)."""
from benchmark.harness.driver import stage_count, stage_ms


def read(ctx):
    if not stage_count(ctx, "ingest_inline"):
        return None
    return 100.0 * stage_ms(ctx, "ingest_inline", "ingest",
                            "stash_retry") / (ctx["wall_s"] * 1000.0)
