"""The check of a DGO configuration: the program's answers against the
plain numpy reference, ``bench/reference.py``.

After the window has closed, a sample of the answered requests, drawn
from the seed (an equal share of each problem, and the request that took
the most iterations), is solved again by ``reference.run`` from the same
start points. Three numbers are compared, each the worst over the
sample, each with its limit from the configuration file:

* ``f_gap``: |best_f - reference best_f| / max(1, |reference best_f|);
* ``x_gap``: |f(best_x) - best_f| / max(1, |best_f|), f in float64: the
  value reported is the value of the point reported;
* ``iters_gap``: |iterations - reference iterations| / reference
  iterations.

The control is the same reference in bfloat16, the precision below the
float32 the configurations state, put in the program's place on the
cell's own traffic; it has to fail. That every request due in the window
was answered without an error is ``run.py``'s rule, for every check.
"""
from __future__ import annotations

import math

import numpy as np

import reference
import traffic as traffic_gen
from drive import Answer


def sample(answers, problems: list, seed: int, size: int) -> list[int]:
    """Indices of answered requests to check: up to ``size / #problems``
    of each problem, drawn from the seed, and the longest request."""
    rng = np.random.default_rng(traffic_gen.seed_sequence(seed, 3))
    done = [i for i, a in enumerate(answers) if a.best_f is not None]
    per = math.ceil(size / len(problems))
    picked = []
    for k in range(len(problems)):
        idx = [i for i in done if answers[i].problem == k]
        picked += [int(i) for i in rng.permutation(idx)[:per]]
    if done:
        longest = max(done, key=lambda i: answers[i].iterations)
        if longest not in picked:
            picked.append(longest)
    return sorted(picked)


def reference_answers(config: dict, answers, indices, dtype="float32"):
    """The reference's answers for ``indices``, in ``dtype``."""
    out = {}
    for i in indices:
        a = answers[i]
        out[i] = reference.run(config["problems"][a.problem], a.x0,
                               max_bits=int(config["max_bits"]),
                               bits_step=int(config["bits_step"]),
                               max_iters=int(config["max_iters"]),
                               dtype=np.float32 if dtype == "float32"
                               else dtype)
    return out


def numbers(config: dict, answers, refs: dict) -> dict:
    """The compared numbers, worst over the checked requests."""
    f_gap = x_gap = it_gap = 0.0
    for i, ref in refs.items():
        a = answers[i]
        spec = config["problems"][a.problem]
        f_gap = max(f_gap, abs(a.best_f - ref.best_f)
                    / max(1.0, abs(ref.best_f)))
        x_gap = max(x_gap, abs(reference.value64(spec, a.best_x) - a.best_f)
                    / max(1.0, abs(a.best_f)))
        it_gap = max(it_gap, abs(a.iterations - ref.iterations)
                     / max(1, ref.iterations))
    return {"f_gap": f_gap, "x_gap": x_gap, "iters_gap": it_gap}


def check(config: dict, run, seed: int) -> tuple[bool, dict]:
    """``(verdict, {name: (number, limit)})`` for a finished run."""
    idx = sample(run.answers, config["problems"], seed,
                 int(config["check_sample"]))
    got = numbers(config, run.answers,
                  reference_answers(config, run.answers, idx))
    limits = config["limits"]
    compared = {k: (v, float(limits[k])) for k, v in got.items()}
    verdict = bool(idx) and all(v <= lim for v, lim in compared.values())
    return verdict, compared


def starts(cell, seed: int, seconds: float) -> list:
    """The requests of the cell's traffic, unanswered: an open loop's
    window at the mix's rate, a closed loop's first ``check_sample + 1``
    starts."""
    cfg, mix = cell.config, cell.traffic
    if mix["loop"] == "closed":
        [entry] = cfg["problems"]
        return [Answer(0, traffic_gen.closed_loop_start(entry, seed, i))
                for i in range(int(cfg["check_sample"]) + 1)]
    return [Answer(a.problem, a.x0) for a in
            traffic_gen.open_loop(mix, cfg["problems"], seed, seconds)]


def control_numbers(cell, seed: int, seconds: float):
    """(numbers, limits) of the bfloat16 control on one seed: the check's
    sample of the cell's traffic, each answered by the bfloat16 reference
    and compared, as a run compares the program, with the float32 one."""
    cfg = cell.config
    answers = starts(cell, seed, seconds)
    for a in answers:              # every request counts as answered
        a.best_f, a.iterations = 0.0, 0
    idx = sample(answers, cfg["problems"], seed, int(cfg["check_sample"]))
    low = reference_answers(cfg, answers, idx, dtype="bfloat16")
    for i, ref in low.items():
        answers[i].best_x = ref.best_x
        answers[i].best_f = float(ref.best_f)
        answers[i].iterations = ref.iterations
    got = numbers(cfg, answers, reference_answers(cfg, answers, idx))
    return got, {k: float(v) for k, v in cfg["limits"].items()}
