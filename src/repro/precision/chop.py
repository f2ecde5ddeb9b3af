"""Round-to-format emulation ("pychop in JAX").

Emulates storage in a reduced floating-point format while computing in a
wider *carrier* dtype (float32 on TPU, float64 on host for the paper's FP64
experiments). Rounding is round-to-nearest, ties-to-even (RNE), with correct
handling of subnormals (of both the target format and the carrier),
underflow-to-zero, overflow (to inf, or saturation for fp8 formats), signed
zeros, infs and NaNs.

The implementation is **pure integer bit manipulation** on the carrier's IEEE
representation. This is deliberate:
  * XLA:CPU runs with DAZ/FTZ, so float arithmetic cannot even observe
    carrier-subnormal values (x != 0 is False for subnormal x!);
  * jnp.frexp / jnp.ldexp / jnp.exp2 are approximate or subnormal-broken;
  * the identical integer algorithm is the body of the Pallas TPU kernel
    (kernels/chop), making this module its bit-exact oracle.

Two entry points:
  chop_static(x, fmt)   — format fixed at trace time.
  chop(x, fmt_id)       — format id is runtime data (traced integer). A single
                          compiled program serves every precision action,
                          which is what makes bandit exploration
                          recompile-free (DESIGN.md §3.4).

Algorithm (elementwise, on bit patterns):
  decompose |x| = M · 2^(Eeff - BIAS - MBITS)   (M includes the implicit bit)
  e      = floor(log2 |x|) = msb(M) + Eeff - BIAS - MBITS
  q      = max(e, emin) - (t - 1)               (target quantum exponent)
  s      = number of low bits of M below the quantum
  Mr     = RNE(M >> s)                          (add half-1 + lsb, shift)
  y      = Mr · 2^q, reassembled into carrier bits (normal or subnormal)
  y      = ±inf (or ±xmax for saturating formats) where |y| > xmax
  0, ±inf, NaN pass through; exact values (s <= 0) pass through.
"""
from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .formats import (FMT_EMAX, FMT_EMIN, FMT_SATURATE, FMT_T, FMT_XMAX,
                      FORMAT_LIST, FloatFormat, get_format)

# Carrier descriptions: (uint dtype, word bits, mantissa bits, exp bias,
# max exponent field).
_CARRIERS = {
    jnp.dtype(jnp.float32): (jnp.uint32, 32, 23, 127, 255),
    jnp.dtype(jnp.float64): (jnp.uint64, 64, 52, 1023, 2047),
}

# xmax bit patterns per format, per carrier (positive magnitude patterns).
_F32_MAX = float(np.finfo(np.float32).max)
FMT_XMAX_BITS32 = np.array(
    [np.float32(min(f.xmax, _F32_MAX)).view(np.uint32)
     for f in FORMAT_LIST], dtype=np.uint32)
FMT_XMAX_BITS64 = np.array(
    [np.float64(f.xmax).view(np.uint64) for f in FORMAT_LIST],
    dtype=np.uint64)


def _carrier(dtype):
    """(uint dtype, word bits, mantissa bits, bias, max exponent field)
    of a carrier dtype. A TPU has no f64 carrier: rounding one bitcasts
    f64 to u64, which the TPU compiler does not implement, so it is
    refused here, while tracing, instead of failing in the compiler."""
    if dtype not in _CARRIERS:
        raise TypeError(f"unsupported carrier dtype {dtype}")
    if dtype == jnp.dtype(jnp.float64) and jax.default_backend() == "tpu":
        raise TypeError(
            "the f64 carrier does not run on a TPU (its rounding bitcasts "
            "f64 to u64, which the TPU compiler does not implement); serve "
            "the f32 carrier: the 'pallas' backend, which is the TPU "
            "default, or JnpBackend(carrier_dtype='float32')")
    return _CARRIERS[dtype]


def _chop_core(x: jnp.ndarray, t, emin, emax, xmax_bits, saturate) -> jnp.ndarray:
    """Elementwise round-to-format on the carrier's bit patterns.

    t/emin/emax are python ints or traced int32 scalars; xmax_bits is the bit
    pattern of the format's xmax in the carrier's uint type; saturate is
    bool-like.

    Every constant carries an explicit dtype (numpy scalars, never Python
    literals): this body also runs inside the Pallas kernels, where a
    weakly typed literal becomes a 64-bit integer under x64 and Mosaic
    cannot lower it."""
    dtype = x.dtype
    UINT, W, MBITS, BIAS, EFMAX = _carrier(dtype)
    u = np.dtype(UINT).type
    i32 = np.int32
    one = u(1)
    u_zero = u(0)
    sign_mask = u(1 << (W - 1))
    frac_mask = u((1 << MBITS) - 1)
    inf_bits = u(EFMAX << MBITS)
    w_max = i32(W - 1)

    t = jnp.asarray(t, jnp.int32)
    emin = jnp.asarray(emin, jnp.int32)
    xmax_bits = jnp.asarray(xmax_bits, UINT)

    bits = lax.bitcast_convert_type(x, UINT)
    sign = bits & sign_mask
    mag = bits & ~sign_mask
    E = (mag >> u(MBITS)).astype(jnp.int32)
    frac = mag & frac_mask

    special = E == i32(EFMAX)     # inf / nan
    zero = mag == u_zero
    is_sub = E == i32(0)

    M = jnp.where(is_sub, frac, frac | u(1 << MBITS))
    Eeff = jnp.where(is_sub, i32(1), E)
    base = Eeff - i32(BIAS + MBITS)                    # |x| = M * 2^base
    Mg = jnp.where(M == u_zero, one, M)                # guard clz for zeros
    msb = w_max - lax.clz(Mg).astype(jnp.int32)
    e_x = msb + base

    q = jnp.maximum(e_x, emin) - (t - i32(1))
    s = q - base                                       # bits to round off
    sc = jnp.clip(s, i32(0), w_max).astype(UINT)
    scm1 = jnp.clip(s - i32(1), i32(0), w_max).astype(UINT)
    lsb = (Mg >> sc) & one
    round_add = jnp.where(s > i32(0), ((one << scm1) - one) + lsb, u_zero)
    Mr = (Mg + round_add) >> sc
    # Full underflow: s >= W would be clipped by sc; |x| < 2^(q-1) there, so
    # the correctly-rounded result is zero.
    Mr = jnp.where(s > w_max, u_zero, Mr)
    exact = s <= i32(0)                                # already representable

    # --- reassemble Mr * 2^q into carrier bits -----------------------------
    zero_r = Mr == u_zero
    Mr_g = jnp.where(zero_r, one, Mr)
    msb_r = w_max - lax.clz(Mr_g).astype(jnp.int32)
    new_e = msb_r + q
    emin_car = 1 - BIAS
    sub_res = new_e < i32(emin_car)

    shift_n = i32(MBITS) - msb_r                       # in [-1, MBITS]
    left = jnp.clip(shift_n, i32(0), w_max).astype(UINT)
    right = jnp.clip(-shift_n, i32(0), w_max).astype(UINT)
    frac_n = ((Mr_g << left) >> right) & frac_mask
    bits_n = ((new_e + i32(BIAS)).astype(UINT) << u(MBITS)) | frac_n

    k_sub = jnp.clip(q - i32(emin_car - MBITS), i32(0),
                     w_max).astype(UINT)
    bits_s = Mr_g << k_sub                             # exponent field 0

    out_mag = jnp.where(sub_res, bits_s, bits_n)
    out_mag = jnp.where(zero_r, u_zero, out_mag)

    over = out_mag > xmax_bits
    sat_mag = jnp.where(jnp.asarray(saturate, bool), xmax_bits, inf_bits)
    out_mag = jnp.where(over, sat_mag, out_mag)

    out_bits = jnp.where(special | zero | exact, bits, sign | out_mag)
    return lax.bitcast_convert_type(out_bits, dtype)


def fma_barrier(x: jnp.ndarray) -> jnp.ndarray:
    """Identity on values, opaque to FMA contraction (DESIGN.md §6.2).

    `_chop_core`'s integer-bitcast chain is what pins the bits of every
    *chopped* intermediate; this applies the same chain to values that
    must stay unrounded (carrier accumulations) by rounding to the
    carrier's OWN format — RNE of an f64 to 53 significand bits (or an
    f32 to 24) is exact, so the value is untouched while the product is
    materialized through real, data-dependent integer arithmetic that
    no simplifier can cancel. Without it, XLA may contract the
    producing multiply into a following add/reduction as an FMA
    depending on each program's fusion context, shifting the
    accumulated bits (measured). Weaker barriers do not survive
    compilation: a bitcast round trip is cancelled by the algebraic
    simplifier, and `lax.optimization_barrier` is elided before fusion
    on XLA:CPU, after which the emitter contracts anyway (both
    measured — a padded and an unpadded solve of the same system
    disagreed in the final residual only under jit).
    """
    x = jnp.asarray(x)
    _, _, MBITS, _, _ = _carrier(x.dtype)
    f = get_format("fp64" if x.dtype == jnp.dtype(jnp.float64) else "fp32")
    assert f.t == MBITS + 1     # carrier-exact: rounding is the identity
    return _chop_core(x, f.t, f.emin, f.emax, _fmt_xmax_bits(f, x.dtype),
                      False)


def tree_sum(x: jnp.ndarray, axis: int = -1,
             keepdims: bool = False) -> jnp.ndarray:
    """Sum along `axis` with a FIXED pairwise reduction tree.

    `jnp.sum` lowers to an XLA reduce whose accumulation order is
    implementation-defined — and it *varies with the compilation
    context* (plain jit vs a shard_map body, measured on XLA:CPU), so
    two programs tracing identical ops can disagree in the low bits of
    a carrier accumulation. Floating-point adds are not associative and
    XLA never re-associates *explicit* adds, so a halving tree of
    explicit adds pins the order in any context: fold the upper half
    onto the lower half, log2(n) times. Odd widths park their last
    element in a running tail accumulator added once at the end — no
    `concatenate`, deliberately, since this also runs inside the Pallas
    qmv kernel body and sub-lane concatenates are a Mosaic lowering
    risk. Every unrounded carrier reduction on the solver hot path goes
    through this (DESIGN.md §6.2, §7.3).

    `keepdims=True` leaves the reduced axis as a size-1 axis in place —
    the same adds in the same order, in the layout a kernel stores."""
    x = jnp.asarray(x)
    axis = axis % x.ndim
    x = jnp.moveaxis(x, axis, -1)
    if x.shape[-1] == 0:
        out = jnp.zeros(x.shape[:-1] + (1,), x.dtype)
    else:
        tail = None
        while x.shape[-1] > 1:
            n = x.shape[-1]
            m = n // 2
            if n % 2:
                last = x[..., n - 1:n]
                tail = last if tail is None else tail + last
            x = x[..., :m] + x[..., m:2 * m]
        out = x if tail is None else x + tail
    return jnp.moveaxis(out, -1, axis) if keepdims else out[..., 0]


def _fmt_xmax_bits(f: FloatFormat, dtype) -> int:
    if dtype == jnp.dtype(jnp.float64):
        return int(np.float64(f.xmax).view(np.uint64))
    return int(np.float32(min(f.xmax, _F32_MAX)).view(np.uint32))


def chop_static(x: jnp.ndarray, fmt: Union[str, FloatFormat]) -> jnp.ndarray:
    """Round `x` (carrier float array) to `fmt`, format fixed at trace time."""
    f = get_format(fmt)
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(f"chop expects float carrier, got {x.dtype}")
    if jnp.finfo(x.dtype).nmant + 1 <= f.t and f.name in ("fp32", "fp64"):
        return x  # identity fast-path: carrier no wider than target
    return _chop_core(x, f.t, f.emin, f.emax, _fmt_xmax_bits(f, x.dtype),
                      f.saturate)


def chop(x: jnp.ndarray, fmt_id) -> jnp.ndarray:
    """Round `x` to the format selected by the (possibly traced) integer id."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(f"chop expects float carrier, got {x.dtype}")
    fmt_id = jnp.asarray(fmt_id, jnp.int32)
    t = jnp.asarray(FMT_T)[fmt_id]
    emin = jnp.asarray(FMT_EMIN)[fmt_id]
    emax = jnp.asarray(FMT_EMAX)[fmt_id]
    if x.dtype == jnp.dtype(jnp.float64):
        xmax_bits = jnp.asarray(FMT_XMAX_BITS64)[fmt_id]
    else:
        xmax_bits = jnp.asarray(FMT_XMAX_BITS32)[fmt_id]
    saturate = jnp.asarray(FMT_SATURATE)[fmt_id]
    return _chop_core(x, t, emin, emax, xmax_bits, saturate)


def chop_stochastic(x: jnp.ndarray, fmt_id, key) -> jnp.ndarray:
    """Stochastic rounding to the format (beyond-paper: unbiased rounding
    for gradient compression / accumulation — E[chop_sr(x)] == x).

    Integer formulation: with s bits to drop, add U ~ uniform[0, 2^s) before
    truncating — exactly SR. Carrier-subnormal/overflow handling matches
    the RNE path."""
    x = jnp.asarray(x)
    if x.dtype != jnp.dtype(jnp.float32):
        raise TypeError("chop_stochastic targets the f32 carrier")
    fmt_id = jnp.asarray(fmt_id, jnp.int32)
    t = jnp.asarray(FMT_T)[fmt_id]
    emin = jnp.asarray(FMT_EMIN)[fmt_id]
    xmax_bits = jnp.asarray(FMT_XMAX_BITS32)[fmt_id]
    saturate = jnp.asarray(FMT_SATURATE)[fmt_id]

    UINT, W, MBITS, BIAS, EFMAX = _CARRIERS[x.dtype]
    one = jnp.asarray(1, UINT)
    bits = lax.bitcast_convert_type(x, UINT)
    sign_mask = one << (W - 1)
    frac_mask = (one << MBITS) - 1
    sign = bits & sign_mask
    mag = bits & ~sign_mask
    E = (mag >> MBITS).astype(jnp.int32)
    frac = mag & frac_mask
    special = E == EFMAX
    zero = mag == 0
    is_sub = E == 0
    M = jnp.where(is_sub, frac, frac | (one << MBITS))
    Eeff = jnp.where(is_sub, 1, E)
    base = Eeff - (BIAS + MBITS)
    Mg = jnp.where(M == 0, one, M)
    msb = (W - 1) - lax.clz(Mg).astype(jnp.int32)
    q = jnp.maximum(msb + base, emin) - (t - 1)
    s = q - base
    sc = jnp.clip(s, 0, W - 1).astype(UINT)
    u = jax.random.bits(key, x.shape, UINT) & ((one << sc) - 1)
    Mr = (Mg + u) >> sc
    Mr = jnp.where(s > W - 1, jnp.zeros((), UINT), Mr)  # deep underflow
    exact = s <= 0
    # Reassemble via the shared path: reuse _chop_core's tail by building a
    # float from Mr * 2^q with overflow/saturation checks.
    zero_r = Mr == 0
    Mr_g = jnp.where(zero_r, one, Mr)
    msb_r = (W - 1) - lax.clz(Mr_g).astype(jnp.int32)
    new_e = msb_r + q
    emin_car = 1 - BIAS
    sub_res = new_e < emin_car
    shift_n = MBITS - msb_r
    left = jnp.clip(shift_n, 0, W - 1).astype(UINT)
    right = jnp.clip(-shift_n, 0, W - 1).astype(UINT)
    frac_n = ((Mr_g << left) >> right) & frac_mask
    bits_n = ((new_e + BIAS).astype(UINT) << MBITS) | frac_n
    k_sub = jnp.clip(q - (emin_car - MBITS), 0, W - 1).astype(UINT)
    bits_s = Mr_g << k_sub
    out_mag = jnp.where(sub_res, bits_s, bits_n)
    out_mag = jnp.where(zero_r, jnp.zeros((), UINT), out_mag)
    inf_bits = jnp.asarray(EFMAX, UINT) << MBITS
    over = out_mag > xmax_bits
    out_mag = jnp.where(over, jnp.where(saturate, xmax_bits, inf_bits),
                        out_mag)
    out_bits = jnp.where(special | zero | exact, bits, sign | out_mag)
    return lax.bitcast_convert_type(out_bits, x.dtype)


def chop_tree(tree, fmt_id):
    """Apply `chop` to every float leaf of a pytree (runtime format id)."""
    def _leaf(v):
        v = jnp.asarray(v)
        if jnp.issubdtype(v.dtype, jnp.floating):
            return chop(v, fmt_id)
        return v
    return jax.tree_util.tree_map(_leaf, tree)


def rounding_unit(fmt_id, dtype=jnp.float32) -> jnp.ndarray:
    """Unit roundoff 2^-t for a (possibly traced) format id."""
    t = jnp.asarray(FMT_T)[jnp.asarray(fmt_id, jnp.int32)]
    # 2^-t for t in [3, 53]: exact via integer exponent assembly.
    if dtype == jnp.dtype(jnp.float64):
        bits = (1023 - t.astype(jnp.int64)) << 52
        return lax.bitcast_convert_type(bits, jnp.float64)
    bits = (127 - t) << 23
    return lax.bitcast_convert_type(bits, jnp.float32)


def chop_matmul(a: jnp.ndarray, b: jnp.ndarray, fmt_id,
                chop_inputs: bool = True,
                chop_output: bool = True) -> jnp.ndarray:
    """Matmul with operands (and result) stored in the emulated format;
    accumulation happens in the carrier dtype — matching MXU semantics
    (bf16 x bf16 -> fp32 accumulate) and FMA-style simulation.

    This is the pure-jnp counterpart of kernels/qmatmul.
    """
    if chop_inputs:
        a = chop(a, fmt_id)
        b = chop(b, fmt_id)
    out = jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    if chop_output:
        out = chop(out, fmt_id)
    return out


def simulate_dtype(x: jnp.ndarray, fmt: Union[str, FloatFormat]) -> jnp.ndarray:
    """Bit-exact native cast when the host has the dtype, else chop_static.

    Used by tests to cross-validate chop against XLA's native casts.
    """
    f = get_format(fmt)
    if f.native_dtype is not None:
        native = jnp.dtype(f.native_dtype)
        if jnp.finfo(native).bits <= jnp.finfo(x.dtype).bits:
            return x.astype(native).astype(x.dtype)
    return chop_static(x, f)
