"""Milliseconds per tick in which some operation ran on the busiest chip:
the union of its device-op intervals in the traced window, per tick."""


def read(ctx):
    if not ctx.ticks or not ctx.devices:
        return None
    return ctx.busiest.busy_ns / 1e6 / ctx.ticks
