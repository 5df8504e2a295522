"""Updates the fan-out sent for each one it encoded, over the SPATIAL and
ENTITY channels: Δ``fanout_sends_total`` over Δ``fanout_encodes_total``.
A window's update is encoded once and sent to every subscriber that
shares it, so this is how many subscribers shared an encode. Nothing
where the program has no such counter, or encoded nothing."""
from benchmark.harness.gateway import total


def read(ctx):
    sends = encodes = 0.0
    for channel_type in ("SPATIAL", "ENTITY"):
        sends += total(ctx["metrics"], "fanout_sends_total",
                       channel_type=channel_type)
        encodes += total(ctx["metrics"], "fanout_encodes_total",
                         channel_type=channel_type)
    return sends / encodes if encodes else None
