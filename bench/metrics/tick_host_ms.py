"""Mean host time of one engine tick (layer: engine): the tick's wall
time less the seconds it waited on the device for the results of its
dispatches, from the program's tick log (serving.telemetry.Telemetry
ticks and their `wait_s`).  The chip idles through it: admission, page
securing, building the dispatch's inputs and the bookkeeping after."""
from bench.scopes import tick_host_s


def read(ctx):
    host = tick_host_s(ctx.ticks)
    return None if host is None else host * 1e3
