"""Solve front door: a solve's host time outside its wait on the device.
The mean, over the program's ``dgo.solve`` spans that lie whole in the
traced window, of the span's length less the ``dgo.solve.fetch`` spans
inside it (on its thread, within its interval): the front door's Python,
the inputs' placement, the engine's dispatch and the result's assembly.
None where the trace holds no ``dgo.solve`` span."""


def read(ctx):
    t = ctx.run.trace
    solves = [] if t is None else t.whole_spans("dgo.solve")
    if not solves:
        return None
    fetches = [s for s in t.program_spans if s.name == "dgo.solve.fetch"]
    host = 0.0
    for s in solves:
        waited = sum(f.end_s - f.start_s for f in fetches
                     if f.thread == s.thread and f.start_s >= s.start_s
                     and f.end_s <= s.end_s)
        host += s.end_s - s.start_s - waited
    return 1e3 * host / len(solves)
