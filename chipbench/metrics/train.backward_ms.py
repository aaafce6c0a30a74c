"""Device milliseconds a step spends in the subset's backward (under the
program's ``train_grad`` scope, a ``transpose(...)`` path, remat's
recompute left out), from the traced window's operations by scope path
(``chipbench/scopes.py``), over the window's steps."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, ("backward",))
