"""Device milliseconds per call of the selection-only program
(``make_train_step(..., kind="select")``: the selection forward over all
rows, features, gradient embeddings, MaxVol and the rank choice), run on
the cell's state and batch after the traced window, from its own trace."""


def read(ctx):
    s = ctx["selection_s"]
    return None if s is None else 1e3 * s
