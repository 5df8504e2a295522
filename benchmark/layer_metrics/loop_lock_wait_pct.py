"""Share of the window's wall time the loop thread waited to win the
interpreter lock back at its timed release site: after its wait
(``loop_poll_seconds_total{part="relock"}``). Lock waits at other sites
(asyncio's own socket calls, the interpreter's forced switches) are not
in it. Nothing where the program does not count them."""
from benchmark.harness.gateway import total


def read(ctx):
    m = ctx["metrics"]
    if not any(name == "loop_poll_seconds_total" for name, _ in m):
        return None
    relock = total(m, "loop_poll_seconds_total", part="relock")
    return 100.0 * relock / ctx["wall_s"]
