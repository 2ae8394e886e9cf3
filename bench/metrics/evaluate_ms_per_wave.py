"""Engine: device time a wave of the operations in which the objective is
evaluated: those whose HLO instructions, the ones XLA fused into them
included, run the program's ``dgo.evaluate`` or ``dgo.parent_eval``
scope. A fusion counts whole, so the decode matmul that XLA fuses the
objective into counts with it. Over the module runs lying whole in the
traced window that evaluate (a served wave is one run of the wave
engine), so that time and waves are of the same runs. None where no
such run was traced."""
import devtrace

SCOPES = ("dgo.evaluate", "dgo.parent_eval")


def read(ctx):
    t = ctx.run.trace
    per_run = [] if t is None else [
        devtrace.time_in(r.scope_time, SCOPES) for r in t.module_runs]
    per_run = [s for s in per_run if s > 0]
    if not per_run:
        return None
    return 1e3 * sum(per_run) / len(per_run)
