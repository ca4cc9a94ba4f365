"""Share (%) of the traced window in which no operation ran on the device,
averaged over the cell's chips."""


def read(ctx):
    red = ctx["reduced"]
    busy = sum(red.busy_ps) / len(red.busy_ps)
    return 100.0 * (1.0 - busy / red.window_ps)
