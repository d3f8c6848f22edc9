"""Device program executions per tick on the busiest chip, from the
profiler trace's program line: how many times a round trip to the device
starts a program."""


def read(ctx):
    if not ctx.ticks or not ctx.devices:
        return None
    return ctx.busiest.launches / ctx.ticks
