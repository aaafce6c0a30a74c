"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's op intervals) / window, averaged over the
chips, from the device trace."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
