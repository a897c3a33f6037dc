"""Device: the share of the traced window in which no operation ran on
the chip, 100 * (1 - union of operation intervals / window).  Moves
itl_p95_ms."""


def read(ctx):
    r = ctx.reduced
    return 100.0 * (1.0 - r.busy_s / r.window_s)
