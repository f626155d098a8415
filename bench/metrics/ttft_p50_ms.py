"""Median time to first token, from the client's side, over every
request due in the window (layer: frontend)."""
import math


def read(ctx):
    v = ctx.nearest_rank([r.ttft for r in ctx.window.records], 50)
    return v * 1e3 if math.isfinite(v) else None
