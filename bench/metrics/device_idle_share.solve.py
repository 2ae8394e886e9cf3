"""Device: share of the traced window in which no operation ran on the
cell's chips (busy is the union of each chip's operations' intervals,
averaged over the chips). Between two solves the chips wait on the
host's work around the engine."""


def read(ctx):
    t = ctx.run.trace
    return None if t is None else 100.0 * t.idle_share
