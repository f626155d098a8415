"""Output tokens the clients received inside the window, over the
window's length (end to end, host clock): those of every request sent,
the lead-in's too."""


def read(ctx):
    win = ctx.window
    return sum(r.in_window for r in win.served) / win.seconds
