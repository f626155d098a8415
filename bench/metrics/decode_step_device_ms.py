"""Device time of one decode step (layer: model step): the device time
of every program the engine dispatched under its decode span (the step
and the small input conversions before it), in the device trace, over
the number of decode spans."""

SPANS = ("paged.decode", "dense.decode")


def read(ctx):
    progs = [p for s in SPANS for p in ctx.trace.label(s)]
    n = sum(ctx.trace.spans[s] for s in SPANS)
    if not n or not progs:
        return None
    return sum(p["device_s"] for p in progs) / n * 1e3
