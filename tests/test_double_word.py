"""The double-word f32 carrier (DESIGN.md §13): its primitives against
numpy float64, its rounding against the benchmark's reference rounding,
GMRES-IR on it against the benchmark's plain reference at 48 bits, a
planted fault that must fail that comparison, the flush predicates, and
the f32 carrier's program left free of it."""
import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.precision import (FORMAT_ID, DoubleWord, JnpBackend,
                             PallasBackend, dw)
from repro.solvers import ir
from repro.solvers.ir import IRConfig, dw_branches, gmres_ir_batch

CHIP = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip")


def _chip_module(name):
    if CHIP not in sys.path:
        sys.path.insert(0, CHIP)
    spec = importlib.util.spec_from_file_location(
        f"dwtest_{name.replace('/', '_')}", os.path.join(CHIP, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


refmath = _chip_module("refmath")
correctness = _chip_module("correctness")
reference = _chip_module("references/gmres_ir_dw")
randsvd = _chip_module("generators/randsvd")

CONFIG = _chip_module("bench").load_json(
    os.path.join(CHIP, "configs", "dense_gmres_ir_dw.json"))
SOLVER = CONFIG["solver"]
LIMIT = CONFIG["correct"]["answer_gap"]
CFG = IRConfig(**SOLVER)


def _values(kind, shape, seed):
    """float64 values: standard normal, or spread over 2^-40..2^40."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape)
    if kind == "ill_scaled":
        x = x * 2.0 ** rng.integers(-40, 41, shape)
    return x


def _pair(x) -> dw.DW:
    a = dw.stack_host(np.asarray(x)[None])[0]
    return dw.DW(jnp.asarray(a[0]), jnp.asarray(a[1]))


# -- primitives -------------------------------------------------------------

@pytest.mark.parametrize("kind", ["normal", "ill_scaled"])
@pytest.mark.parametrize("name", ["two_sum", "two_prod"])
def test_error_free_transformation_is_exact(name, kind):
    a = _values(kind, 4096, 1).astype(np.float32)
    b = _values(kind, 4096, 2).astype(np.float32)
    s, e = jax.jit(getattr(dw, name))(a, b)
    want = (np.float64(a) + np.float64(b) if name == "two_sum"
            else np.float64(a) * np.float64(b))
    got = np.float64(np.asarray(s)) + np.float64(np.asarray(e))
    assert np.array_equal(got, want)
    # The pair is normalized: its high word is its value rounded.
    assert np.array_equal(np.asarray(s), want.astype(np.float32))


def _op_case(name, kind):
    """(jitted op, its DW operands, float64 value, error scale)."""
    x = _pair(_values(kind, 2048, 3))
    y = _pair(_values(kind, 2048, 4))
    xv, yv = dw.to_f64(x), dw.to_f64(y)
    if name == "add":
        return dw.add, (x, y), xv + yv, np.abs(xv) + np.abs(yv)
    if name == "mul":
        return dw.mul, (x, y), xv * yv, np.abs(xv * yv)
    if name == "div":
        return dw.div, (x, y), xv / yv, np.abs(xv / yv)
    if name == "sqrt":
        x = dw.tmap(jnp.abs, x)
        v = np.sqrt(dw.to_f64(x))
        return dw.sqrt, (x,), v, v
    X = _pair(_values(kind, (64, 384), 5))
    Y = _pair(_values(kind, (64, 384), 6))
    Xv, Yv = dw.to_f64(X), dw.to_f64(Y)
    if name == "dot":
        return dw.dot, (X, Y), (Xv * Yv).sum(-1), np.abs(Xv * Yv).sum(-1)
    A = _pair(_values(kind, (384, 384), 7))
    v = dw.tmap(lambda a: a[0], Y)
    Av, vv = dw.to_f64(A), dw.to_f64(v)
    return dw.matvec, (A, v), Av @ vv, np.abs(Av) @ np.abs(vv)


@pytest.mark.parametrize("kind", ["normal", "ill_scaled"])
@pytest.mark.parametrize("name", ["add", "mul", "div", "sqrt", "dot",
                                  "matvec"])
def test_dw_op_relative_error_is_within_2_to_the_minus_44(name, kind):
    fn, args, want, scale = _op_case(name, kind)
    got = dw.to_f64(jax.jit(fn)(*args))
    assert np.all(np.abs(got - want) <= 2.0 ** -44 * scale)


@pytest.mark.parametrize("n", [36, 64])
@pytest.mark.parametrize("lower", [True, False])
def test_dw_trisolve_matches_float64(lower, n):
    # Diagonally dominant factors in one LU array, so the substitution
    # is well conditioned; n = 36 pads to whole blocks.
    rng = np.random.default_rng(n)
    M = rng.standard_normal((n, n)) / n + np.diag(1.0 + rng.random(n))
    LU, v = _pair(M), _pair(rng.standard_normal(n))
    F, vv = dw.to_f64(LU), dw.to_f64(v)
    T = np.tril(F, -1) + np.eye(n) if lower else np.triu(F)
    want = np.linalg.solve(T, vv)
    got = dw.to_f64(jax.jit(dw.trisolve, static_argnames="lower")(
        LU, v, lower=lower))
    assert np.max(np.abs(got - want)) <= 2.0 ** -44 * np.max(np.abs(want))


@pytest.mark.parametrize("n", [5, 36, 64])
def test_dw_lu_solve_matches_both_substitutions(n):
    # One traced substitution in two passes, the second on the reversed
    # factors: U^-1 L^-1 v as the lower then the upper `trisolve` and
    # float64 give it. The pair's low words may differ in their last
    # bits, where XLA contracts a cross term of `mul` in one program
    # and not in the other.
    rng = np.random.default_rng(n)
    M = rng.standard_normal((n, n)) / n + np.diag(1.0 + rng.random(n))
    LU, v = _pair(M), _pair(rng.standard_normal(n))
    F = dw.to_f64(LU)
    want = np.linalg.solve(np.triu(F), np.linalg.solve(
        np.tril(F, -1) + np.eye(n), dw.to_f64(v)))
    two = dw.trisolve(LU, dw.trisolve(LU, v, lower=True), lower=False)
    got = dw.lu_solve(dw.lu_factors(LU), v)
    np.testing.assert_array_equal(np.asarray(got.hi), np.asarray(two.hi))
    scale = 2.0 ** -44 * np.max(np.abs(want))
    assert np.max(np.abs(dw.to_f64(got) - want)) <= scale
    assert np.max(np.abs(dw.to_f64(got) - dw.to_f64(two))) <= scale


def _chop_inputs(t, kind):
    """DW pairs and their float64 values: random pairs, or pairs whose
    high word lies exactly halfway between two t-bit numbers, with a
    low word below, at or above zero."""
    rng = np.random.default_rng(t)
    if kind == "random":
        hi = _values("ill_scaled", 3000, t).astype(np.float32)
        frac = rng.uniform(-1, 1, hi.shape) * 2.0 ** -24
        lo = (np.float64(hi) * frac).astype(np.float32)
    else:
        m = rng.integers(2 ** (t - 1), 2 ** t, 1000).astype(np.float64)
        scale = 2.0 ** rng.integers(-20, 20, m.shape)
        mid = (m + 0.5) * 2.0 ** -(t - 1) * scale     # t+1 bits: exact
        hi = np.repeat(mid, 3).astype(np.float32)
        ulp = np.spacing(np.abs(hi)) * 0.25
        lo = (np.tile([-1.0, 0.0, 1.0], m.size) * ulp).astype(np.float32)
        hi = hi * np.tile([1, -1, 1], m.size).astype(np.float32)
    s, e = dw.fast_two_sum(jnp.asarray(hi), jnp.asarray(lo))
    x = dw.DW(s, e)
    return x, dw.to_f64(x)


@pytest.mark.parametrize("kind", ["random", "ties"])
@pytest.mark.parametrize("fmt", ["bf16", "tf32", "fp32"])
def test_dw_chop_rounds_the_pair_correctly(fmt, kind):
    t = refmath.T_BITS[fmt]
    x, value = _chop_inputs(t, kind)
    got = jax.jit(dw.chop)(x, FORMAT_ID[fmt])
    assert np.all(np.asarray(got.lo) == 0)
    assert np.array_equal(np.float64(np.asarray(got.hi)),
                          refmath.chop(value, t))


def test_dw_chop_at_fp64_is_the_identity():
    x, _ = _chop_inputs(8, "random")
    got = jax.jit(dw.chop)(x, FORMAT_ID["fp64"])
    assert np.array_equal(np.asarray(got.hi), np.asarray(x.hi))
    assert np.array_equal(np.asarray(got.lo), np.asarray(x.lo))


@pytest.mark.parametrize("fmt", ["bf16", "fp32", "fp64"])
@pytest.mark.parametrize("op", ["chop", "chop_mv", "chop_matmul",
                                "chop_trisolve"])
def test_double_word_backend_serves_pairs_and_delegates_f32(op, fmt):
    rng = np.random.default_rng(11)
    n = 24
    A = _pair(rng.standard_normal((n, n)) + n * np.eye(n))
    v = _pair(rng.standard_normal(n))
    f = FORMAT_ID[fmt]
    bk = DoubleWord(JnpBackend())
    # on pairs: the dw module's ops, rounded to the format
    c = lambda x: dw.chop(x, f)
    want = {"chop": lambda: c(A),
            "chop_mv": lambda: c(dw.matvec(c(A), c(v))),
            "chop_matmul": lambda: c(jax.lax.map(
                lambda row: dw.matvec(dw.tmap(lambda m: m.T, c(A)), row),
                c(A))),
            "chop_trisolve": lambda: c(dw.trisolve(A, c(v), lower=False))}
    args = {"chop": (A, f), "chop_mv": (A, v, f), "chop_matmul": (A, A, f),
            "chop_trisolve": (A, v, f)}
    kw = {"chop_trisolve": {"lower": False}}.get(op, {})
    got = getattr(bk, op)(*args[op], **kw)
    assert np.array_equal(dw.to_f64(got), dw.to_f64(want[op]()))
    # plain f32 arrays go to the base backend unchanged
    plain = tuple(a.hi if isinstance(a, dw.DW) else a for a in args[op])
    assert np.array_equal(np.asarray(getattr(bk, op)(*plain, **kw)),
                          np.asarray(getattr(bk.base, op)(*plain, **kw)))


# -- GMRES-IR on the carrier against the plain reference ----------------------

ARMS = {"fp64": ("fp64",) * 4,
        "fp64_residual": ("fp32", "fp32", "fp32", "fp64"),
        "fp64_gmres": ("bf16", "fp32", "fp64", "fp64"),
        "fp32": ("fp32",) * 4}
N = 48
# (arm, kappa): each LU factors its system stably, kappa u_f < 1, the
# pairs the benchmark's comparison samples.
CASES = [("fp64", 1e2), ("fp64", 1e5), ("fp64", 1e9),
         ("fp64_residual", 1e2), ("fp64_residual", 1e5),
         ("fp64_gmres", 1e1), ("fp32", 1e2), ("fp32", 1e5)]
KAPPAS = sorted({k for _, k in CASES})


def _systems():
    rng = np.random.default_rng(16)
    out = {}
    for kappa in KAPPAS:
        A, b, x = randsvd.make(N, kappa, rng, {"sigma_max": 1.0})
        out[kappa] = (A, b, x)
    return out


def _solve_cases(backend):
    systems = _systems()
    A = np.stack([systems[k][0] for _, k in CASES])
    b = np.stack([systems[k][1] for _, k in CASES])
    x = np.stack([systems[k][2] for _, k in CASES])
    acts = np.array([[FORMAT_ID[f] for f in ARMS[a]] for a, _ in CASES],
                    np.int32)
    st = jax.device_get(gmres_ir_batch(A, b, x, acts, cfg=CFG,
                                       backend=backend))
    return systems, st


@pytest.fixture(scope="module")
def solved():
    return {"jnp": _solve_cases(DoubleWord(JnpBackend())),
            "pallas-interpret": _solve_cases(
                DoubleWord(PallasBackend(interpret=True)))}


def _gap(systems, st, k, arm, kappa):
    """(program status, reference status, answer gap) of case k."""
    A, b, x = systems[kappa]
    names = ARMS[arm]
    ref = reference.solve(A, b, x, correctness.bits(names, 48),
                          refmath.T_BITS[names[1]], SOLVER)
    fb, nb = refmath.error_bounds(refmath.T_BITS[names[1]],
                                  refmath.T_BITS[names[3]], N,
                                  correctness.kappa_inf(A), 48)
    gap = max(correctness.gap(float(st.ferr[k]), ref[1], fb),
              correctness.gap(float(st.nbe[k]), ref[2], nb))
    return int(st.status[k]), int(ref[0]), gap


@pytest.mark.parametrize("backend", ["jnp", "pallas-interpret"])
@pytest.mark.parametrize("k", range(len(CASES)),
                         ids=[f"{a}-{k:.0e}" for a, k in CASES])
def test_dw_gmres_ir_matches_the_reference_at_48_bits(solved, backend, k):
    systems, st = solved[backend]
    arm, kappa = CASES[k]
    status, ref_status, gap = _gap(systems, st, k, arm, kappa)
    assert status == ref_status
    assert gap <= LIMIT


@pytest.mark.parametrize("k", [0, 3])
def test_fp64_answers_are_fp64_accurate(solved, k):
    # The all-fp64 and the fp64-residual arms converge to a backward
    # error no f32 residual reaches (2^-24 is 6e-8).
    st = solved["jnp"][1]
    assert int(st.status[k]) == ir.CONVERGED
    assert float(st.nbe[k]) < (1e-13 if CASES[k][0] == "fp64" else 1e-8)


@pytest.fixture
def lo_dropped(monkeypatch):
    """Every DW normalization keeps its high word only: the carrier's
    arithmetic is f32's. Traced anew under a backend value no other test
    uses, and the compiled programs are dropped afterwards."""
    jax.clear_caches()          # the DW ops are jitted: trace them anew
    monkeypatch.setattr(dw, "fast_two_sum",
                        lambda a, b: (a + b, jnp.zeros_like(a + b)))
    yield DoubleWord(JnpBackend(carrier_dtype="float32"))
    jax.clear_caches()


@pytest.mark.parametrize("k", [0, 4], ids=["fp64", "fp64_residual"])
def test_dropping_the_low_word_fails_the_comparison(lo_dropped, k):
    systems, st = _solve_cases(lo_dropped)
    arm, kappa = CASES[k]
    status, ref_status, gap = _gap(systems, st, k, arm, kappa)
    assert status != ref_status or gap > LIMIT


# -- the flush predicates -----------------------------------------------------

def _action_sets():
    rng = np.random.default_rng(4)
    ids = [FORMAT_ID[f] for f in ("bf16", "tf32", "fp32", "fp64")]
    out = []
    for rows in (1, 3, 8):
        for _ in range(4):
            a = np.sort(rng.choice(ids, size=(rows, 4)), axis=1)
            out.append(a.astype(np.int32))
    return out


@pytest.mark.parametrize("k", range(12))
def test_host_flush_flags_equal_the_device_predicate(k):
    from repro.tasks import GMRESIRTask
    acts = _action_sets()[k]
    task = GMRESIRTask(backend=DoubleWord(JnpBackend()))
    host = task.flush_tags(list(acts))
    dev = jax.device_get(jax.jit(dw_branches)(jnp.asarray(acts)))
    assert (host["dw_lu"], host["dw_gmres"]) == (bool(dev[0]),
                                                 bool(dev[1]))
    # pad rows repeat row 0, so a padded batch answers the same
    padded = np.concatenate([acts] + [acts[:1]] * 3)
    assert task.flush_tags(list(padded)) == host
    assert GMRESIRTask(backend=JnpBackend()).flush_tags(list(acts)) == {}


def _conds(jaxpr, inside=()):
    """(primitive path, cond eqn) of every cond in a jaxpr, nested."""
    out = []
    for eqn in jaxpr.eqns:
        path = inside + (eqn.primitive.name,)
        if eqn.primitive.name == "cond":
            out.append((inside, eqn))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else [v]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    out += _conds(getattr(inner, "jaxpr", inner), path)
    return out


@pytest.mark.parametrize("branch", ["lu", "gmres"])
def test_dw_branches_stay_conds_under_the_batch_vmap(branch):
    # vmap turns a cond whose predicate is batched into a select that
    # runs both branches. The flush predicates are computed outside the
    # vmap, so both stay conds: the LU's at the top of the batch, the
    # GMRES one inside the vmapped refinement loop, with a scalar
    # predicate.
    n, B = 16, 2
    args = (jnp.zeros((B, 2, n, n), jnp.float32),
            jnp.zeros((B, 2, n), jnp.float32),
            jnp.zeros((B, 2, n), jnp.float32), jnp.zeros((B, 4), jnp.int32))
    bk = DoubleWord(JnpBackend())
    closed = jax.make_jaxpr(lambda *a: ir._gmres_ir_dw_batch(
        *a, dataclasses.replace(CFG, m_max=3), bk))(*args)
    conds = _conds(closed.jaxpr)
    if branch == "lu":
        found = [e for path, e in conds if path == ()]
    else:
        found = [e for path, e in conds if "while" in path
                 and "map" not in path and "scan" not in path]
    assert found
    assert all(e.invars[0].aval.shape == () for e in found)


@pytest.mark.parametrize("backend", ["jnp-f32", "pallas-interpret", "dw"])
def test_the_f32_carrier_traces_no_double_word_code(backend, monkeypatch):
    calls = []
    for name in ("two_prod", "two_sum", "chop", "trisolve"):
        fn = getattr(dw, name)
        monkeypatch.setattr(dw, name, lambda *a, _f=fn, _n=name, **k: (
            calls.append(_n), _f(*a, **k))[1])
    bk = {"jnp-f32": JnpBackend(carrier_dtype="float32"),
          "pallas-interpret": PallasBackend(interpret=True),
          "dw": DoubleWord(JnpBackend())}[backend]
    # a configuration no other test traces, so this lowering traces
    cfg = dataclasses.replace(CFG, m_max=5, i_max=3)
    n, B = 16, 2
    low = ir.gmres_ir_batch_lowerable(cfg, bk)
    args = low.bind((np.tile(np.eye(n), (B, 1, 1)), np.ones((B, n)),
                     np.ones((B, n)), np.zeros((B, 4), np.int32)))
    low.lower(args)
    assert bool(calls) == (backend == "dw")


# -- the served path ----------------------------------------------------------

def test_served_path_answers_at_fp64_and_counts_dw_flushes():
    from repro.core import AutotuneEngine, reduced_action_space
    from repro.core.features import system_features
    from repro.core.rewards import RewardConfig
    from repro.data.matrices import LinearSystem
    from repro.obs import MetricsRegistry, Observability
    from repro.service import AutotuneServer, BatcherConfig, OnlineConfig
    from repro.tasks import GMRESIRTask

    rng = np.random.default_rng(9)
    systems = []
    for n, kappa in ((20, 1e3), (24, 1e6), (28, 1e8)):
        A, b, x = randsvd.make(n, kappa, rng, {"sigma_max": 1.0})
        systems.append(LinearSystem(A, b, x, kappa, system_features(A),
                                    "dw"))
    space = reduced_action_space(("bf16", "tf32", "fp32", "fp64"), 4)
    task = GMRESIRTask(systems, space, ir_cfg=dataclasses.replace(
        CFG, m_max=20), bucket_step=32, min_bucket=32,
        backend=DoubleWord(JnpBackend()))
    # An unvisited state resolves to the highest arm: all fp64.
    policy = AutotuneEngine(task).fit_policy((3, 3))
    obs = Observability(registry=MetricsRegistry())
    srv = AutotuneServer(policy, task, reward_cfg=RewardConfig(),
                         batcher_cfg=BatcherConfig(max_batch=4,
                                                   bucket_step=32,
                                                   min_bucket=32),
                         online_cfg=OnlineConfig(eps0=0.0, eps_min=0.0,
                                                     eps_boost=0.0, alpha=0.0),
                         seed=0, obs=obs)
    ids = [srv.submit(s) for s in systems]
    srv.drain()
    resp = [srv.poll(i) for i in ids]
    for r in resp:
        assert r.action_names == ("fp64",) * 4
        assert r.record.status == ir.CONVERGED
        assert r.record.metrics["nbe"] < 1e-13
    (root,) = [s for s in obs.tracer.spans(cat="flush")
               if s.name == "flush"]
    assert root.args["dw_lu"] is True and root.args["dw_gmres"] is True
    samples = {}
    for fam in obs.registry.collect():
        if fam.name in ("repro_solver_dw_flushes_total",
                        "repro_solver_rows_total"):
            samples[fam.name] = [(dict(zip(fam.labelnames, key)),
                                  child.value)
                                 for key, child in fam.samples()]
    branches = {lab["branch"]: v for lab, v in
                samples["repro_solver_dw_flushes_total"]}
    assert branches == {"lu": 1.0, "gmres": 1.0}
    (rows,) = samples["repro_solver_rows_total"]
    assert rows[0]["carrier"] == "double-word" and rows[1] == 4.0
