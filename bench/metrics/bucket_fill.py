"""Scheduler: share of dispatched wave slots that carried a request, over
the waves the window finished (the scheduler's slot counters, as deltas
over the window)."""


def read(ctx):
    c = ctx.run.counters
    if not c.get("slots"):
        return None
    return 100.0 * (c["slots"] - c["padded_slots"]) / c["slots"]
