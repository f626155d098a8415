"""90th percentile (nearest rank) over every request due in the window
of the time between its output tokens, (last - first) / (tokens - 1),
from the client's side (end to end, host clock).  A request that never
finishes counts as +inf."""


def read(ctx):
    return ctx.nearest_rank([r.tpot for r in ctx.window.records], 90) * 1e3
