"""Loaders shared by the benchmark's scripts: everything is found by the
name `BENCHMARK.json` gives it, so a new configuration, traffic mix,
task or per-layer metric is a new file and never an edit here.

    configs/<config>.json      a deployment (generator, solver, batcher)
    traffic/<traffic>.json     a traffic mix (entry, loop, pool, load)
    generators/<kind>.py       make(n, kappa, rng, params) -> (A, b, x)
    tasks/<task>.py            build(config) -> the program's task
    references/<task>.py       plain numpy solve of the same semantics
    metrics/<metric>.py        read(record) -> number or None
"""
import importlib.util
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = REPO) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(name: str, root: str = REPO, here: str = HERE) -> dict:
    """The cell `name` with its configuration and traffic files loaded."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    cell = with_files(cells[name], here)
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def with_files(cell: dict, here: str = HERE) -> dict:
    """The cell with its configuration and traffic files loaded."""
    cell = dict(cell)
    cell["config_file"] = load_json(os.path.join(
        here, "configs", cell["config"] + ".json"))
    cell["traffic_file"] = load_json(os.path.join(
        here, "traffic", cell["traffic"] + ".json"))
    return cell


def module(kind: str, name: str, here: str = HERE):
    """Import `<kind>/<name>.py` (names may hold dots)."""
    path = os.path.join(here, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def design(config: dict, count: int):
    """`count` stratified (n, kappa) pairs, the same for every run seed:
    a Latin hypercube over the generator's n range and log10 kappa
    range, drawn from the configuration's design seed."""
    gen = config["generator"]
    rng = np.random.default_rng(int(config["assumed"]["design_seed"]))
    lo, hi = gen["n"]
    klo, khi = gen["log10_kappa"]
    pn = rng.permutation(count)
    pk = rng.permutation(count)
    un, uk = rng.random(count), rng.random(count)
    ns = [int(math.floor(lo + (pn[i] + un[i]) / count * (hi - lo + 1)))
          for i in range(count)]
    ks = [10.0 ** (klo + (pk[i] + uk[i]) / count * (khi - klo))
          for i in range(count)]
    return [(min(n, hi), k) for n, k in zip(ns, ks)]


def make_pool(config: dict, count: int):
    """The deployment's systems: the design's (n, kappa) pairs, with
    matrices and right-hand sides drawn from the configuration's design
    seed, so that every run serves the same set (a run's seed orders
    it). Returns dicts with A, b, x_true (float64 numpy), n and the
    generator's kappa."""
    gen = module("generators", config["generator"]["kind"])
    rng = np.random.default_rng(int(config["assumed"]["design_seed"]) + 2)
    pool = []
    for n, kappa in design(config, count):
        A, b, x = gen.make(n, kappa, rng, config["generator"]["params"])
        pool.append({"A": A, "b": b, "x_true": x, "n": n, "kappa": kappa})
    return pool


def latencies(rec: dict):
    """Submit-to-answer seconds of every request the window sent; a
    request that failed, expired or was never answered counts as an
    infinite latency."""
    return [a["t_done"] - a["t_submit"]
            if a["status"] != 3 and not a["expired"] else np.inf
            for a in rec["answers"]] + [np.inf] * rec["unanswered"]


def percentile(values, q: float) -> float:
    """q-th percentile: the smallest value with at least q% of the
    values at or below it."""
    return float(np.percentile(np.asarray(values, np.float64), q,
                               method="inverted_cdf"))
