"""Shape/format sweeps: fused qmatmul kernel vs pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.qmatmul import qmatmul_op, qmatmul_ref, qmatmul_ref_blocked
from repro.precision import FORMAT_ID, FORMATS

RNG = np.random.default_rng(11)

SHAPES = [(32, 128, 128), (64, 256, 128), (100, 130, 70), (8, 512, 256),
          (256, 512, 256)]
FMTS = ["e5m2", "e4m3", "bf16", "fp16", "tf32", "fp32"]


def _mats(M, K, N):
    a = jnp.asarray(RNG.standard_normal((M, K)).astype(np.float32))
    b = jnp.asarray(RNG.standard_normal((K, N)).astype(np.float32))
    return a, b


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("fmt", FMTS)
def test_qmatmul_vs_blocked_ref(shape, fmt):
    """Bit-exact for coarse output formats; for fine formats, XLA's gemm
    reduction order varies with tile shape, so the bound is the f32
    accumulation noise plus one output ulp."""
    M, K, N = shape
    bk = 128
    a, b = _mats(M, K, N)
    got = np.asarray(qmatmul_op(a, b, FORMAT_ID[fmt], bm=32, bn=128, bk=bk,
                                interpret=True))
    Kp = -(-K // bk) * bk
    ap = jnp.pad(a, ((0, 0), (0, Kp - K)))
    bp = jnp.pad(b, ((0, Kp - K), (0, 0)))
    want = np.asarray(qmatmul_ref_blocked(ap, bp, FORMAT_ID[fmt], bk))
    f = FORMATS[fmt]
    if f.t <= 8:
        np.testing.assert_array_equal(got, want)
    else:
        scale = np.abs(want) + np.sqrt(K)
        tol = 4 * f.unit_roundoff + 8 * np.sqrt(K) * np.finfo(np.float32).eps
        assert np.max(np.abs(got - want) / scale) <= tol


@pytest.mark.parametrize("fmt", ["bf16", "fp32"])
def test_qmatmul_close_to_mathematical_ref(fmt):
    """Accumulation-order differences stay within ~1 output ulp."""
    a, b = _mats(128, 512, 128)
    got = np.asarray(qmatmul_op(a, b, FORMAT_ID[fmt], bm=64, bn=128, bk=128,
                                interpret=True))
    want = np.asarray(qmatmul_ref(a, b, FORMAT_ID[fmt]))
    u = FORMATS[fmt].unit_roundoff
    scale = np.abs(want) + np.sqrt(512)
    tol = 4 * u + 8 * np.sqrt(512) * np.finfo(np.float32).eps
    assert np.max(np.abs(got - want) / scale) <= tol


def test_qmatmul_emulates_precision_loss():
    a, b = _mats(64, 128, 64)
    exact = np.asarray(a @ b)
    lo = np.asarray(qmatmul_op(a, b, FORMAT_ID["e4m3"], bm=32, bn=128,
                               bk=128, interpret=True))
    hi = np.asarray(qmatmul_op(a, b, FORMAT_ID["fp32"], bm=32, bn=128,
                               bk=128, interpret=True))
    err_lo = np.abs(lo - exact).mean()
    err_hi = np.abs(hi - exact).mean()
    assert err_lo > 10 * err_hi


def test_qmatmul_chop_out_flag():
    a, b = _mats(32, 128, 128)
    with_chop = np.asarray(qmatmul_op(a, b, FORMAT_ID["bf16"],
                                      chop_out=True, bm=32, bn=128, bk=128,
                                      interpret=True))
    no_chop = np.asarray(qmatmul_op(a, b, FORMAT_ID["bf16"],
                                    chop_out=False, bm=32, bn=128, bk=128,
                                    interpret=True))
    # Unchopped accumulator has values not representable in bf16.
    from repro.precision import chop_static
    assert np.array_equal(
        np.asarray(chop_static(jnp.asarray(no_chop), "bf16")), with_chop)
    assert not np.array_equal(with_chop, no_chop)
