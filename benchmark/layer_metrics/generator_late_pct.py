"""Share of the window's updates that the benchmark's own senders sent
more than one send frame after they could have gone (their due time, or
the read of the handover they had to wait for). A starved generator must
not be read as a fast gateway."""


def read(ctx):
    g = ctx["generator"]
    if not g["updates_in_window"]:
        return None
    return 100.0 * g["late"] / g["updates_in_window"]
