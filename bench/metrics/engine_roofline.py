"""Engine: the least time of the DGO work of the solves traced whole, on
the cell's chips, over the chips' busy time in the traced window (mean
over the chips). The work comes from shapes and the objective alone
(``work.py``), with the iterations per resolution that the plain
reference takes from the same starts; nearly all device time in a solve
cell is the engine, so busy time stands for it whatever the program
names it. None where no solve was traced whole or the chip has no
peaks."""
import reference
import work


def read(ctx):
    run, t = ctx.run, ctx.run.trace
    if t is None or not run.traced or not t.busy_s or not ctx.peak:
        return None
    cfg = ctx.cell.config
    total = work.Work(0.0, 0.0)
    for i in run.traced:
        a = run.answers[i]
        spec = cfg["problems"][a.problem]
        ref = reference.run(spec, a.x0, max_bits=int(cfg["max_bits"]),
                            bits_step=int(cfg["bits_step"]),
                            max_iters=int(cfg["max_iters"]))
        schedule = reference.resolutions(int(spec["bits"]),
                                         int(cfg["max_bits"]),
                                         int(cfg["bits_step"]))
        total += work.solve(spec, schedule, ref.per_resolution)
    return 100.0 * total.least_s(ctx.peak) / t.n_devices / t.busy_s
