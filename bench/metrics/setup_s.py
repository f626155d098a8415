"""Process start to the opening of the window: weights, the serving
stack, the warm-up that compiles or loads every program the window
runs, and the cell's lead-in of traffic, if it has one (end to end,
host clock)."""


def read(ctx):
    return ctx.setup_s
