"""Training tokens consumed per second over the whole window: the steps
the window ran times the global batch's tokens, over the window's
seconds by the host clock (from one block on the device to the next)."""


def read(ctx):
    win, cell = ctx["window"], ctx["cell"]
    return win.steps * cell.batch * cell.seq / win.window_s
