"""What decides `correct`: the program's answers against the plain
reference of the configuration's task, on the systems and actions the
timed window answered.

An answer carries (status, ferr, nbe) and the action it ran; the
reference re-solves the same system under the same action
(references/<task>.py). Two numbers are compared, each with its limit
from the configuration's "correct" block:

  answer_gap    the worst, over the answers of a seeded sample of the
                distinct (system, action) pairs the window answered
                (the largest system always in it), of |ferr - ferr_ref|
                and |nbe - nbe_ref|, each over the accuracy bound of that
                system and action (refmath.error_bounds), and infinite
                where one side failed and the other did not. Every
                answer of a sampled pair is compared with the pair's one
                reference solve. Rounding and summation order move a
                sound answer well inside the bound; a lower precision, a
                wrong action or an answer that was never solved moves it
                by more.
                Only pairs whose LU precision factors the system
                stably are sampled: kappa_inf(A) u_f < 1, kappa from the
                reference's own condition number. Above that the
                factorization is of a numerically singular matrix, and
                whether it meets a zero pivot, or where the refinement
                stagnates, is decided by rounding, in the program and
                in the reference alike.
  bound_excess  the worst, over every converged answer, of ferr and nbe
                over their bounds: a converged answer meets the accuracy
                the configuration states (limit 1).
"""
import numpy as np

import bench
from refmath import T_BITS, error_bounds

FAILED = 3


def kappa_inf(A: np.ndarray) -> float:
    return float(np.linalg.cond(A, np.inf))


def bits(names, carrier_t: int):
    return tuple(min(T_BITS[n], carrier_t) for n in names)


def reference(config: dict, system: dict, names, carrier_t: int):
    """(status, ferr, nbe, n_outer, n_inner) of the reference solve."""
    ref = bench.module("references", config["task"])
    return ref.solve(system["A"], system["b"], system["x_true"],
                     bits(names, carrier_t), T_BITS[names[1]],
                     config["solver"])


def gap(a, b, bound) -> float:
    if a == b:
        return 0.0
    d = abs(a - b)
    return float(d / bound) if np.isfinite(d) else float("inf")


class Kappas(dict):
    """kappa_inf of each pool system, computed once."""

    def __init__(self, pool):
        super().__init__()
        self.pool = pool

    def __missing__(self, i):
        self[i] = kappa_inf(self.pool[i]["A"])
        return self[i]


def bounds(config: dict, pool, kap, a):
    names = a["action_names"]
    return error_bounds(T_BITS[names[1]], T_BITS[names[3]],
                        pool[a["i"]]["n"], kap[a["i"]],
                        int(config["carrier_t"]))


def stable(config: dict, kap, a) -> bool:
    """The answer's LU precision factors its system stably."""
    t_f = min(T_BITS[a["action_names"][0]], int(config["carrier_t"]))
    return kap[a["i"]] * 2.0 ** -t_f < 1.0


def key(a) -> tuple:
    return a["i"], tuple(a["action_names"])


def sample(config: dict, kap, answers, count: int, seed: int):
    """A seeded sample of the distinct (system, action) pairs of the
    answers that `stable` admits, the largest system always first. A
    request refused or timed out at the front door was not answered: it
    counts as failed, not as wrong."""
    first = {}
    for k, a in enumerate(answers):
        if a.get("code", 200) == 200 and not a.get("expired"):
            first.setdefault(key(a), k)
    keys = [k for k in sorted(first.values())
            if stable(config, kap, answers[k])]
    if not keys:
        return []
    top = max(keys, key=lambda k: (answers[k]["n"], -k))
    rest = [k for k in keys if k != top]
    rng = np.random.default_rng(seed)
    pick = rng.permutation(len(rest))[:max(count - 1, 0)]
    return [key(answers[top])] + [key(answers[rest[j]])
                                  for j in sorted(pick)]


def rows(config: dict, pool, kap, answers):
    """Each answer beside the reference's, with its gap; the reference
    solves each (system, action) pair once."""
    out, refs = [], {}
    for a in answers:
        if key(a) not in refs:
            refs[key(a)] = reference(config, pool[a["i"]],
                                     a["action_names"],
                                     int(config["carrier_t"]))
        st, ferr, nbe, _, _ = refs[key(a)]
        fb, nb = bounds(config, pool, kap, a)
        g = max(gap(a["ferr"], ferr, fb), gap(a["nbe"], nbe, nb))
        if (a["status"] == FAILED) != (st == FAILED):
            g = float("inf")
        out.append({"n": a["n"], "kappa_inf": kap[a["i"]],
                    "action": list(a["action_names"]),
                    "answer": [a["status"], a["ferr"], a["nbe"]],
                    "reference": [st, ferr, nbe], "bounds": [fb, nb],
                    "gap": g})
    return out


def excess(config: dict, pool, kap, answers) -> float:
    worst = 0.0
    for a in answers:
        if a["status"] == 0:
            fb, nb = bounds(config, pool, kap, a)
            worst = max(worst, a["ferr"] / fb, a["nbe"] / nb)
    return worst


def control(config: dict, pool, answers):
    """The control: the reference put in the program's place, computed
    on the next narrower carrier than the configuration states
    (`control_t`, bfloat16's 8 bits for the f32 carrier), on the same
    systems under the same actions: one answer per (system, action)
    pair."""
    out = []
    for a in answers:
        st, ferr, nbe, _, inner = reference(config, pool[a["i"]],
                                            a["action_names"],
                                            int(config["control_t"]))
        out.append(dict(a, status=st, ferr=ferr, nbe=nbe, inner=inner))
    return out


def compare(config: dict, pool, answers, seed: int):
    """(numbers, rows): the numbers compared, each {value, limit}, and
    the sampled answers beside the reference. `answers` are dicts with
    i (pool index), n, action_names, status, ferr and nbe."""
    kap = Kappas(pool)
    keys = set(sample(config, kap, answers,
                      int(config["correct"]["sample"]), seed))
    detail = rows(config, pool, kap, [a for a in answers
                                      if key(a) in keys])
    lim = config["correct"]
    return {"answer_gap": {"value": max((r["gap"] for r in detail),
                                        default=0.0),
                           "limit": lim["answer_gap"]},
            "bound_excess": {"value": excess(config, pool, kap, answers),
                             "limit": lim["bound_excess"]}}, detail


def verdict(numbers: dict) -> bool:
    """Every number at or under its limit; a limit not yet set fails."""
    return all(v["limit"] is not None and np.isfinite(v["value"])
               and v["value"] <= v["limit"] for v in numbers.values())
