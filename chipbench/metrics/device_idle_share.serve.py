"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 1 - busy / window."""


def read(ctx):
    r = ctx.reduced
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
