"""Mean host wall time of one engine tick, admission and prefill of new
requests included (layer: engine), from the program's tick log
(serving.telemetry.Telemetry.ticks)."""


def read(ctx):
    if not ctx.ticks:
        return None
    return sum(d for _, d, _ in ctx.ticks) / len(ctx.ticks) * 1e3
