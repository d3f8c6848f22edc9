"""The ranking kernel's share of its roofline, in %: the least time the
chip could take for the calls' necessary work (``harness.roofline``: the
larger of operations over peak FLOP/s and bytes over peak bytes/s, summed
over calls) over the kernel's device time on the chip that ran them.  On
several chips the work is split over them, so the least time is divided by
the chips and compared with the busiest chip's kernel time."""

from harness import roofline


def read(ctx):
    if not ctx.rank_calls or not ctx.devices or ctx.peak is None:
        return None
    kernel_ns = ctx.busiest.kernel_ns
    if kernel_ns <= 0:
        return None
    least = sum(roofline.least_time(o, b, ctx.peak)[0]
                for o, b in ctx.rank_calls) / ctx.chips
    return 100.0 * least / (kernel_ns / 1e9)
