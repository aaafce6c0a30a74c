"""Seconds from the start of the process to the window's first step:
imports, traffic, weights, compilation or the cache, and the warm-up
steps, by the host clock."""


def read(ctx):
    return ctx["window"].setup_s
