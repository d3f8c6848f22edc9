"""Milliseconds per tick of the ranking kernel (``reid_topk_segments`` or
``reid_topk_tiles``) on the busiest chip: the sum of its device events."""


def read(ctx):
    if not ctx.ticks or not ctx.devices or not ctx.busiest.kernel_calls:
        return None
    return ctx.busiest.kernel_ns / 1e6 / ctx.ticks
