"""Prefill dispatches the engine issued per request completed, over the
whole run of the window (layer: scheduler).  The scheduler writes a
prompt in blocks of at most 16 tokens, one dispatch each."""


def read(ctx):
    a, b = ctx.marks["c_open"], ctx.marks["c_end"]
    done = b["completed"] - a["completed"]
    if done <= 0:
        return None
    return (b["prefill_dispatches"] - a["prefill_dispatches"]) / done
