"""Device: share of the traced window in which no operation ran on the
chip (busy is the union of its operations' intervals)."""


def read(ctx):
    t = ctx.run.trace
    return None if t is None else 100.0 * t.idle_share
