"""Mean host time of one ``ServingEngine.ingest`` call (FrameStore
appends), from the benchmark's own span around each call in the window."""


def read(ctx):
    if not ctx.ingest_s:
        return None
    return 1e3 * sum(ctx.ingest_s) / len(ctx.ingest_s)
