"""Share of the traced window, in %, in which no operation ran on the
busiest chip: 1 - busy / window."""


def read(ctx):
    if not ctx.devices or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busiest.busy_ns / 1e9 / ctx.window_s)
