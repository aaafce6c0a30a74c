"""Rows a step's subset forward and backward ran over: the mean, over the
window's steps, of the program's ``subset_rows`` counter in each step's
metrics row. None for a program that does not report it."""


def read(ctx):
    rows = ctx["rows"]
    if not rows or any(r.get("subset_rows") is None for r in rows):
        return None
    return sum(float(r["subset_rows"]) for r in rows) / len(rows)
