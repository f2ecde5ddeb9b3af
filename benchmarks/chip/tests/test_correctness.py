"""`correct` comes out false for the control and for each fault the
cells can have, and true for sound answers.

The control is the reference on the next narrower carrier (bfloat16
for the configuration's f32) in the program's place. The faults break
the timed path underneath a whole run of the harness (all but its look
for a chip) at a size a CPU holds: a solve that returns its starting
state, half of each batch left out (those rows keep their starting
state), and answers altered where they are produced. One chip holds
the whole batch, so no exchange between chips can be left out."""
import copy
import dataclasses
from types import SimpleNamespace

import numpy as np
import pytest

import bench
import correctness
import run

FAKE_TPU = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def small_config(name):
    cfg = copy.deepcopy(bench.load_json(
        f"{bench.HERE}/configs/{name}.json"))
    cfg["generator"]["n"] = [100, 128]
    cfg["buckets"] = [128]
    cfg["correct"]["sample"] = 8
    return cfg


def ladder_answers(cfg, pool):
    """One answer per system under an arm with an f32 residual and
    update, as the reference gives them."""
    names = [["bf16", "fp32", "fp32", "fp32"], ["tf32", "fp32", "fp64",
             "fp64"], ["fp64"] * 4, ["bf16", "tf32", "fp32", "fp64"]]
    out = []
    for i, s in enumerate(pool):
        a = names[i % len(names)]
        st, ferr, nbe, _, inner = correctness.reference(cfg, s, a, 24)
        out.append({"i": i, "n": s["n"], "action_names": a, "status": st,
                    "ferr": ferr, "nbe": nbe, "inner": inner})
    return out


def test_the_control_is_not_correct():
    cfg = small_config("dense_gmres_ir")
    pool = bench.make_pool(cfg, 6)
    sound = ladder_answers(cfg, pool)
    numbers, detail = correctness.compare(cfg, pool, sound, 3)
    assert detail and numbers["answer_gap"]["value"] == 0.0
    ctrl = correctness.control(cfg, pool, sound)
    numbers, _ = correctness.compare(cfg, pool, ctrl, 3)
    assert numbers["answer_gap"]["value"] > cfg["correct"]["answer_gap"]
    assert not correctness.verdict(numbers)


def untrained_policy(path):
    from repro.core import (Discretizer, PrecisionPolicy, QTable,
                            reduced_action_space)
    space = reduced_action_space()
    disc = Discretizer.fit(np.array([[1.0, -1.0], [10.0, 3.0]]), [4, 4])
    qt = QTable(disc.n_states, space.n_actions, seed=0)
    qt.Q[:] = np.random.default_rng(0).standard_normal(qt.Q.shape)
    qt.N[:] = 1
    PrecisionPolicy(space, disc, qt).save(str(path))


def state_unchanged(outs):
    """x stays at x0 = 0: ferr = nbe = 1, maximum iterations."""
    return [dataclasses.replace(o, status=2, metrics=dict(
        o.metrics, ferr=1.0, nbe=1.0)) for o in outs]


def half_left_out(outs):
    """Only the first half of each batch solved: the rest keep their
    starting state x0 = 0."""
    h = max(len(outs) // 2, 1)
    return outs[:h] + state_unchanged(outs[h:])


def altered(outs):
    """Each answer's errors altered where they are produced."""
    return [dataclasses.replace(o, metrics=dict(
        o.metrics, ferr=o.metrics["ferr"] * 1e3 + 1e-3,
        nbe=o.metrics["nbe"] * 1e3 + 1e-3)) for o in outs]


@pytest.fixture(scope="module")
def policy_dir(tmp_path_factory):
    p = tmp_path_factory.mktemp("policy")
    untrained_policy(p)
    return p


@pytest.mark.parametrize("fault", [None, state_unchanged, half_left_out,
                                   altered])
def test_a_run_with_the_timed_path_broken_is_not_correct(fault, policy_dir):
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.precision import JnpBackend
    entry = bench.module("entries", "inproc")

    def build(cell, seed):
        srv, task, systems, pool = entry.build(
            cell, seed, backend=JnpBackend(carrier_dtype="float32"))
        if fault is not None:
            solve = task.solve_rows
            task.solve_rows = lambda rows, acts, chunk: fault(
                solve(rows, acts, chunk))
        return srv, task, systems, pool

    def entry_run(cell, seed, seconds, trace_dir=None):
        cell["config_file"].update(small_config(cell["config"]))
        cell["config_file"]["policy"] = str(policy_dir)
        cell["traffic_file"] = dict(cell["traffic_file"], pool=12,
                                    outstanding=8)
        return entry.run(cell, seed, seconds, build_fn=build)

    args = SimpleNamespace(workload="dense_gmres.inproc.closed32",
                           seed=2 ** 31 + 99, seconds=2.0, trace=0)
    out = run.run(args, FAKE_TPU, PEAK, entry_run=entry_run)
    ok, numbers = run.decide(out)
    assert out["rec"]["answers"], "the window answered nothing"
    assert ok == (fault is None), numbers
