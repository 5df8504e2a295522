"""How far behind its fan-out window a subscription to a SPATIAL channel
was when it was served, mean over the window's services
(``fanout_window_lag_ms{channel_type="SPATIAL"}``, counted in the
program's ``tick_data``)."""
from benchmark.harness.gateway import total


def read(ctx):
    served = total(ctx["metrics"], "fanout_window_lag_ms_count",
                   channel_type="SPATIAL")
    if not served:
        return None
    return total(ctx["metrics"], "fanout_window_lag_ms_sum",
                 channel_type="SPATIAL") / served
