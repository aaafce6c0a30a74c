"""Device milliseconds a refresh step spends in the selection forward
(the program's ``select_forward`` scope: the forward over all K rows, the
features and the gradient embeddings), from the traced window's
operations by scope path (``chipbench/scopes.py``), over the window's
refresh steps."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, ("select_forward",), refresh=True)
