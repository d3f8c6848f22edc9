"""How unevenly the fleet's chips work: the busiest chip's device busy
time over the mean busy time of the cell's chips, in the traced window.
1.0 is even; on four chips 4.0 means one chip did all the work."""


def read(ctx):
    busy = [d.busy_ns for d in ctx.devices.values()]
    if not busy or not any(busy):
        return None
    return max(busy) / (sum(busy) / len(busy))
