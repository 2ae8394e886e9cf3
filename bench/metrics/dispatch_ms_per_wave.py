"""Dispatch: the mean length of the program's ``dgo.dispatch`` spans (one
a wave, on the scheduler's thread: the pop of a bucket to
``submit_wave``'s return) that lie whole in the traced window. None
where the trace holds none."""


def read(ctx):
    t = ctx.run.trace
    spans = [] if t is None else t.whole_spans("dgo.dispatch")
    if not spans:
        return None
    return 1e3 * sum(s.end_s - s.start_s for s in spans) / len(spans)
