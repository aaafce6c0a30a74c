"""Device milliseconds a step spends updating the state (the program's
``optimizer`` and ``sentinel`` scopes: XLA fuses the sentinel's select
into the update), from the traced window's operations by scope path
(``chipbench/scopes.py``), over the window's steps."""
from chipbench import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, ("update",))
