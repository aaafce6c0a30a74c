"""Host milliseconds a step spends waiting for its batch: the program's
``data_wait`` spans inside the traced window's ``loop`` span, on the
trace's clock (``chipbench/scopes.py``), over the window's steps."""
from chipbench import scopes


def read(ctx):
    sp = scopes.window_split(ctx)
    if sp is None or not sp.data_wait_s:
        return None
    return 1e3 * sp.data_wait_s / ctx["window"].steps
