"""Decides ``correct``: the program's answers against the plain reference.

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

``correct`` also needs every request due in the window answered without
an error.
"""
from __future__ import annotations

import math

import numpy as np

import reference
import traffic as traffic_gen


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
    """``(correct, {name: (number, limit)})`` for a finished run."""
    idx = sample(run.answers, config["problems"], seed,
                 int(config["check_sample"]))
    got = numbers(config, run.answers,
                  reference_answers(config, run.answers, idx))
    limits = config["limits"]
    compared = {k: (v, float(limits[k])) for k, v in got.items()}
    correct = (run.failed == 0 and bool(idx)
               and all(v <= lim for v, lim in compared.values()))
    return correct, compared
