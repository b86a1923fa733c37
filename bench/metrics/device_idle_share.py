"""Share of the traced window in which no operation ran on the chip
(mean over the cell's chips), from the device trace."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
