"""Transactions committed in the window (stats fetched from the device,
summed in int64, all devices) over the window's seconds on the host
clock. The window runs from the first dispatch to the drain's stats on
the host, so all of its work and all of its time are in it."""


def read(ctx):
    return ctx["totals"]["committed"] / ctx["window_s"]
