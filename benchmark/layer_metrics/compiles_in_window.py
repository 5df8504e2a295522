"""Programs added to the persistent compile cache between the window's
first and last instant. Anything but 0 means a shape was not warmed."""


def read(ctx):
    return float(ctx["compiles"])
