"""Running requests forced back to the queue per request completed
(layer: scheduler): lazy page allocation preempts when the pool runs
out."""


def read(ctx):
    a, b = ctx.marks["c_open"], ctx.marks["c_end"]
    done = b["completed"] - a["completed"]
    if done <= 0:
        return None
    return (b["preemptions"] - a["preemptions"]) / done
