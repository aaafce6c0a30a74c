"""Device milliseconds a refresh step spends deciding (the program's
``select_decide`` scope: the fused MaxVol and projection-error sweep, the
rank and the weights), from the traced window's operations by scope path
(``chipbench/scopes.py``), over the window's refresh steps."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, ("select_decide",), refresh=True)
