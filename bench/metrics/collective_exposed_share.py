"""Collective: share of the traced window in which a chip runs the
per-iteration exchange of (value, id) pairs and nothing else (collective
operations by HLO opcode, less the part other operations cover; averaged
over the chips). None where the trace holds no collective."""


def read(ctx):
    t = ctx.run.trace
    if t is None or not t.collective_s:
        return None
    return 100.0 * t.collective_exposed_s / t.window_s
