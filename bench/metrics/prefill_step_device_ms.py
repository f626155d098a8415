"""Device time of one prefill dispatch (layer: model step): the device
time of every program the engine dispatched under its prefill span (a
block of 1 to 16 prompt tokens), over the number of prefill spans."""

SPANS = ("paged.prefill", "dense.prefill")


def read(ctx):
    progs = [p for s in SPANS for p in ctx.trace.label(s)]
    n = sum(ctx.trace.spans[s] for s in SPANS)
    if not n or not progs:
        return None
    return sum(p["device_s"] for p in progs) / n * 1e3
