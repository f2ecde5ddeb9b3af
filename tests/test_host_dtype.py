"""A flush's rows leave the host in the dtype the backend's entry point
takes (`PrecisionBackend.host_dtype`): float32 on the Pallas backend,
whose carrier cast then happens in numpy and not on the device; float64
on `DoubleWord`, whose `prepare` splits them into (hi, lo); as given on
the jnp backend. The answers are the bits the device cast gave, the
AOT warmup builds the executable live flushes run, and the `flush` span
says what crossed (`input_bytes`, `input_dtype`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LocalExecutor, pad_to_bucket, reduced_action_space
from repro.core import executor as EX
from repro.core.batching import solve_fixed_batch
from repro.data.matrices import randsvd_dense
from repro.obs.trace import Tracer, use
from repro.precision import DoubleWord, JnpBackend, PallasBackend, dw
from repro.solvers import IRConfig, gmres_ir_batch_lowerable, ir
from repro.tasks import GMRESIRTask
from repro.tasks.base import stack_fixed, stack_flush

SPACE = reduced_action_space()
BUCKET, CHUNK = 16, 4
BACKENDS = {"jnp": JnpBackend(),
            "pallas-interpret": PallasBackend(interpret=True),
            "double-word": DoubleWord(JnpBackend())}
HOST_DTYPE = {"jnp": np.float64, "pallas-interpret": np.float32,
              "double-word": np.float64}


def _ir(tau):
    # Each compiling test has its own tau: the per-shape executable
    # caches are process-wide, and a test must see its own builds.
    return IRConfig(tau=tau, i_max=4, m_max=12)


def _rows():
    """Three padded systems of bucket 16 under actions whose LU runs in
    bf16, fp32 and fp64, so rows of several formats share a flush."""
    rng = np.random.default_rng(5)
    rows = [pad_to_bucket(randsvd_dense(n, kappa, rng), BUCKET, BUCKET)
            for n, kappa in ((11, 1e2), (13, 1e4), (16, 1e6))]
    acts = [np.asarray(SPACE.actions[i], np.int32)
            for i in (0, 12, len(SPACE.actions) - 1)]
    return rows, acts


def _solve(rows, acts, cfg, backend):
    return solve_fixed_batch([r[0] for r in rows], [r[1] for r in rows],
                             [r[2] for r in rows], acts, cfg, CHUNK,
                             backend=backend)


def _device_cast(rows, acts, cfg, backend):
    """The rows stacked as given (float64) and cast by the entry point's
    `prepare` on the device: the flush path before the host cast."""
    A, b, x, a, k = stack_fixed(rows, acts, CHUNK)
    assert A.dtype == np.float64
    st = LocalExecutor().dispatch(gmres_ir_batch_lowerable(cfg, backend),
                                  (A, b, x, a), BUCKET)
    return [np.asarray(f)[:k] for f in jax.device_get(tuple(st))]


def _bits(v):
    v = np.asarray(v)
    return v.view(np.uint32 if v.itemsize == 4 else np.uint64)


def _assert_records_bit_equal(recs, stats):
    fields = ("ferr", "nbe", "n_outer", "n_gmres", "status", "res_norm")
    for name, col in zip(fields, stats):
        got = np.asarray([getattr(r, name) for r in recs], col.dtype)
        np.testing.assert_array_equal(_bits(got), _bits(col), err_msg=name)


def test_host_cast_rounds_as_the_device_cast():
    # Ties at 24 bits in both directions, signs, and magnitudes far from
    # 1: numpy's cast and the device's convert round to nearest even.
    ulp = 2.0 ** -23
    ties = np.array([1 + ulp / 2, 1 + 3 * ulp / 2, -(1 + ulp / 2),
                     2 + ulp, 3.0 * 2 ** 60 * (1 + ulp / 2),
                     np.pi, -np.e * 1e-30, 1 / 3])
    rng = np.random.default_rng(0)
    for v in (ties, rng.standard_normal(4096),
              rng.standard_normal(512) * 2.0 ** rng.integers(-60, 61, 512)):
        host = np.stack([v], dtype=PallasBackend().host_dtype)[0]
        dev = np.asarray(PallasBackend().coerce(jnp.asarray(v)))
        assert host.dtype == dev.dtype == np.float32
        np.testing.assert_array_equal(_bits(host), _bits(dev))


@pytest.mark.parametrize("name", sorted(BACKENDS))
def test_flush_span_notes_what_crosses_to_the_device(name):
    rows, acts = _rows()
    tracer = Tracer()
    with use(tracer), tracer.span("flush", cat="flush", tid=1):
        A, b, x, a, k = stack_flush(rows, acts, CHUNK, BACKENDS[name])
    want = np.dtype(HOST_DTYPE[name])
    assert k == 3 and A.dtype == b.dtype == x.dtype == want
    assert a.dtype == np.int32
    n_float = CHUNK * (BUCKET * BUCKET + 2 * BUCKET)
    (root,) = [s for s in tracer.spans(cat="flush") if s.name == "flush"]
    assert root.args["input_dtype"] == str(want)
    assert root.args["input_bytes"] == \
        n_float * want.itemsize + CHUNK * 4 * 4
    # The rows' values, rounded once on the host where the dtype narrows.
    np.testing.assert_array_equal(
        A[:k], np.stack([r[0] for r in rows]).astype(want))


@pytest.fixture
def bound(monkeypatch):
    """(dtypes taken, arrays handed on) of every `LowerableCall.bind`:
    what the entry point's `prepare` got from the host and gave the
    executable."""
    calls = []
    bind = EX.LowerableCall.bind

    def spy(self, arrays):
        out = bind(self, arrays)
        calls.append((tuple(np.dtype(a.dtype) for a in arrays[:3]),
                      [np.asarray(a) for a in out[:3]]))
        return out

    monkeypatch.setattr(EX.LowerableCall, "bind", spy)
    return calls


def test_pallas_prepare_takes_f32_and_answers_the_device_cast_bits(bound):
    bk = BACKENDS["pallas-interpret"]
    cfg = _ir(1.3e-6)
    rows, acts = _rows()
    recs = _solve(rows, acts, cfg, bk)
    c0 = EX.executor_compile_count()
    stats = _device_cast(rows, acts, cfg, bk)
    assert EX.executor_compile_count() == c0  # the same executable
    (host_in, host_out), (dev_in, dev_out) = bound
    assert host_in == (np.dtype(np.float32),) * 3
    assert dev_in == (np.dtype(np.float64),) * 3
    # prepare hands on the numpy-made f32 arrays, bit for bit what the
    # device made of the f64 ones.
    want = stack_fixed(rows, acts, CHUNK, dtype="float32")[:3]
    for h, d, w in zip(host_out, dev_out, want):
        assert h.dtype == d.dtype == np.float32
        np.testing.assert_array_equal(_bits(h), _bits(w))
        np.testing.assert_array_equal(_bits(d), _bits(w))
    assert stats[0].dtype == np.float32
    _assert_records_bit_equal(recs, stats)


def test_double_word_rows_reach_the_split_as_float64(monkeypatch):
    bk = BACKENDS["double-word"]
    cfg = _ir(1.4e-6)
    rows, acts = _rows()
    seen = []
    prepare = ir._prepare

    def spy(bk_, A, b, x_true, actions):
        seen.append((A.dtype, b.dtype, x_true.dtype))
        lo = dw.stack_host(A)[:, 1]
        assert np.count_nonzero(lo[: len(rows)]) > 0   # low words kept
        return prepare(bk_, A, b, x_true, actions)

    monkeypatch.setattr(ir, "_prepare", spy)
    recs = _solve(rows, acts, cfg, bk)
    assert seen == [(np.float64,) * 3]
    stats = _device_cast(rows, acts, cfg, bk)
    _assert_records_bit_equal(recs, stats)


def test_jnp_rows_stay_float64_bit_identical(bound):
    bk = BACKENDS["jnp"]
    cfg = _ir(1.5e-6)
    rows, acts = _rows()
    recs = _solve(rows, acts, cfg, bk)
    stats = _device_cast(rows, acts, cfg, bk)
    assert len(bound) == 2
    for taken, handed in bound:
        assert taken == (np.dtype(np.float64),) * 3
        assert all(h.dtype == np.float64 for h in handed)
    assert stats[0].dtype == np.float64
    _assert_records_bit_equal(recs, stats)


@pytest.mark.parametrize("name", ["pallas-interpret", "double-word"])
def test_precompiled_bucket_serves_the_live_flush(name):
    rows, acts = _rows()
    task = GMRESIRTask(action_space=SPACE, ir_cfg=_ir(1.6e-6),
                       bucket_step=BUCKET, min_bucket=BUCKET,
                       backend=BACKENDS[name])
    c0 = EX.executor_compile_count()
    assert task.precompile_bucket(BUCKET, CHUNK)
    assert EX.executor_compile_count() == c0 + 1
    out = task.solve_rows(rows, acts, CHUNK)
    assert len(out) == len(rows)
    assert EX.executor_compile_count() == c0 + 1
