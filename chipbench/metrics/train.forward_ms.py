"""Device milliseconds a step spends in the subset's forward (the
program's ``train_grad`` scope, neither backward nor remat's recompute),
from the traced window's operations by scope path
(``chipbench/scopes.py``), over the window's steps."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, ("forward",))
