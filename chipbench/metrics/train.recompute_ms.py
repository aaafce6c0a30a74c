"""Device milliseconds a step spends in remat's recompute (under the
program's ``train_grad`` scope, a ``rematted_computation`` path), from the
traced window's operations by scope path (``chipbench/scopes.py``), over
the window's steps."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, ("recompute",))
