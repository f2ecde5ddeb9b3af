"""Double-word arithmetic on the f32 carrier (DESIGN.md §13).

A TPU has no f64, so the paper's fp64 steps run on a double-word (DW)
carrier: an unevaluated pair of f32 arrays `hi + lo` with
`hi == fl(hi + lo)`. The pair holds 48 significand bits (24 + 24) with
f32's exponent range. The algorithms are Dekker's (1971) error-free
transformations and the double-word building blocks of Joldes, Muller
and Popescu (ACM TOMS 44(2), 2017); with u = 2^-24:

  two_sum, fast_two_sum   s + e == a + b exactly
  two_prod                p + e == a * b exactly (Dekker's split)
  add                     relative error <= 3 u^2   (their Alg. 6)
  mul                     relative error <= 7 u^2   (Dekker's mul2)
  div, sqrt               one Newton correction through two_prod

Every product whose bits matter goes through `fma_barrier`: XLA may
otherwise contract it into a following add as an FMA, which breaks the
exactness of `two_prod` and of the split. The products of split halves
are exact in f32, so a contraction there changes nothing. The TPU's f32
`divide` and `sqrt` are not correctly rounded; the Newton correction
computes the remainder exactly and absorbs their error.

Sums take a pinned pairwise tree (`sum`, the schedule of
`chop.tree_sum`), so a DW dot or matvec has one reduction order in any
program. `chop` rounds the pair's value correctly to a format of at
most 24 bits, ties included, and is the identity at fp64.

Values are `DW(hi, lo)` named tuples, so they ride through `vmap`,
`lax` loops and `lax.cond` as pytrees. Each DW op is its own `jax.jit`
function: one op is tens of primitives (a barrier alone is a few dozen),
and a solver calls the same op at the same shapes many times, so a
program that uses them traces and lowers each once per shape instead of
at every call; XLA inlines the calls when it compiles."""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .chop import FMT_XMAX_BITS32, _chop_core, fma_barrier
from .formats import FMT_EMAX, FMT_EMIN, FMT_SATURATE, FMT_T

F32 = jnp.float32
# Veltkamp's constant 2^12 + 1: splits a 24-bit significand into two
# halves whose pairwise products are exact in f32.
_SPLITTER = np.float32(4097.0)
_ZERO = np.float32(0.0)
_ONE = np.float32(1.0)
_TWO = np.float32(2.0)


class DW(NamedTuple):
    """A double-word value: `hi + lo`, both f32 arrays of one shape."""
    hi: jnp.ndarray
    lo: jnp.ndarray


# -- construction and structure ---------------------------------------------

def lift(x) -> DW:
    """An f32 array as a DW value (lo = 0)."""
    x = jnp.asarray(x, F32)
    return DW(x, jnp.zeros_like(x))


def zeros(shape) -> DW:
    z = jnp.zeros(shape, F32)
    return DW(z, z)


def tmap(f, *xs) -> DW:
    """Apply a structural op (indexing, reshape, select) to both words."""
    return DW(f(*[x.hi for x in xs]), f(*[x.lo for x in xs]))


def where(c, x: DW, y: DW) -> DW:
    return DW(jnp.where(c, x.hi, y.hi), jnp.where(c, x.lo, y.lo))


def get(x: DW, i, axis: int = 0) -> DW:
    """Entry `i` (an int or a traced index) along `axis`: a dynamic
    slice, not a gather."""
    return tmap(lambda a: lax.dynamic_index_in_dim(a, i, axis, False), x)


def put(x: DW, i, v: DW, axis: int = 0) -> DW:
    """`x` with entry `i` along `axis` set to `v`: a dynamic update
    slice, not a scatter."""
    return DW(*(lax.dynamic_update_index_in_dim(a, jnp.asarray(b, a.dtype),
                                                i, axis)
                for a, b in ((x.hi, v.hi), (x.lo, v.lo))))


def stack_host(a) -> np.ndarray:
    """Split float64 rows (leading axis: batch) into f32 (hi, lo) on a
    new axis 1: hi = fl32(a), lo = fl32(a - hi). Exact to 48 bits, in
    numpy, so the pair crosses the link in the bytes f64 would take and
    no f64 array reaches the device."""
    a = np.asarray(a, np.float64)
    hi = a.astype(np.float32)
    lo = (a - hi.astype(np.float64)).astype(np.float32)
    return np.stack([hi, lo], axis=1)


def unstack(a) -> DW:
    """The DW value of a batch stacked by `stack_host` (pair on axis 1)."""
    return DW(a[:, 0], a[:, 1])


def to_f64(x: DW) -> np.ndarray:
    """The pair's value in float64 (host side, tests)."""
    return (np.asarray(x.hi, np.float64) + np.asarray(x.lo, np.float64))


# -- error-free transformations ---------------------------------------------

def two_sum(a, b):
    """(s, e) with s = fl(a + b) and s + e == a + b exactly (Knuth)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def fast_two_sum(a, b):
    """(s, e) with s + e == a + b exactly, for |a| >= |b| (Dekker)."""
    s = a + b
    return s, b - (s - a)


@jax.jit
def split(a):
    """Veltkamp's split: a == hi + lo, each with at most 12 significand
    bits (the sign absorbs the 24th), so products of halves are exact."""
    c = fma_barrier(_SPLITTER * a)
    hi = c - (c - a)
    return hi, a - hi


@jax.jit
def two_prod(a, b, sa=None, sb=None):
    """(p, e) with p = fl(a * b) and p + e == a * b exactly (Dekker).
    `sa`/`sb` are `split(a)`/`split(b)` when the caller has them."""
    p = fma_barrier(a * b)
    ah, al = split(a) if sa is None else sa
    bh, bl = split(b) if sb is None else sb
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


# -- double-word arithmetic -------------------------------------------------

def neg(x: DW) -> DW:
    return DW(-x.hi, -x.lo)


@jax.jit
def add(x: DW, y: DW) -> DW:
    """Accurate DW + DW (Joldes et al., Alg. 6): relative error <= 3u^2."""
    sh, sl = two_sum(x.hi, y.hi)
    th, tl = two_sum(x.lo, y.lo)
    vh, vl = fast_two_sum(sh, sl + th)
    return DW(*fast_two_sum(vh, tl + vl))


def sub(x: DW, y: DW) -> DW:
    return add(x, neg(y))


@jax.jit
def mul(x: DW, y: DW, sx=None, sy=None) -> DW:
    """DW * DW (Dekker's mul2): the exact product of the high words plus
    the cross terms; relative error <= 7u^2. `sx`/`sy`: `split` of the
    high words, when the caller reuses them."""
    ch, cl1 = two_prod(x.hi, y.hi, sx, sy)
    cl2 = x.hi * y.lo + x.lo * y.hi
    return DW(*fast_two_sum(ch, cl1 + cl2))


@jax.jit
def div(x: DW, y: DW) -> DW:
    """DW / DW: an f32 quotient, its remainder computed exactly through
    two_prod, and one correction from it (a Newton step)."""
    th = x.hi / y.hi
    ph, pl = two_prod(th, y.hi)
    rh = x.hi - ph                      # exact: ph is within 2x of x.hi
    rl = (x.lo - pl) - th * y.lo
    return DW(*fast_two_sum(th, (rh + rl) / y.hi))


@jax.jit
def sqrt(x: DW) -> DW:
    """sqrt of a non-negative DW value: an f32 root and one Newton
    correction from its exactly computed square."""
    sh = jnp.sqrt(x.hi)
    ph, pl = two_prod(sh, sh)
    r = ((x.hi - ph) - pl) + x.lo
    safe = jnp.where(sh == _ZERO, _ONE, sh)
    out = DW(*fast_two_sum(sh, r / (_TWO * safe)))
    return where(x.hi == _ZERO, zeros(jnp.shape(x.hi)), out)


@partial(jax.jit, static_argnames="axis")
def sum(x: DW, axis: int = -1) -> DW:
    """DW sum along `axis` by the pinned halving tree of `tree_sum`:
    fold the upper half onto the lower, log2(n) times; an odd width
    parks its last element in a tail added once at the end."""
    axis = axis % x.hi.ndim
    x = tmap(lambda a: jnp.moveaxis(a, axis, -1), x)
    if x.hi.shape[-1] == 0:
        return zeros(x.hi.shape[:-1])
    tail = None
    while x.hi.shape[-1] > 1:
        n = x.hi.shape[-1]
        m = n // 2
        if n % 2:
            last = tmap(lambda a: a[..., n - 1], x)
            tail = last if tail is None else add(tail, last)
        x = add(tmap(lambda a: a[..., :m], x),
                tmap(lambda a: a[..., m:2 * m], x))
    out = tmap(lambda a: a[..., 0], x)
    return out if tail is None else add(out, tail)


@jax.jit
def dot(x: DW, y: DW) -> DW:
    """sum(x * y) along the last axis: exact products, the pinned tree."""
    return sum(mul(x, y), -1)


@jax.jit
def matvec(A: DW, v: DW, sA=None) -> DW:
    """A @ v for an (..., n, n) matrix: `sA` is `split(A.hi)` when the
    caller reuses the matrix across calls."""
    vb = tmap(lambda a: a[..., None, :], v)
    return sum(mul(A, vb, sA, split(vb.hi)), -1)


@jax.jit
def norm2(v: DW) -> DW:
    """||v||_2 along the last axis."""
    return sqrt(sum(mul(v, v), -1))


@jax.jit
def scale(x: DW, s: DW) -> DW:
    """x * s for a DW scalar s."""
    return mul(x, tmap(lambda a: jnp.broadcast_to(a, x.hi.shape), s))


# -- rounding to a runtime format -------------------------------------------

@jax.jit
def chop(x: DW, fmt_id) -> DW:
    """Round the pair's value to the format `fmt_id` (runtime data).

    At fp64 the pair is the carrier's own value: the identity. At a
    format of t <= 24 bits the result is RN_t(hi + lo) with lo = 0.
    With |lo| <= ulp(hi)/2, hi + lo rounds as hi does unless hi lies
    exactly halfway between two t-bit neighbours; there the sign of lo
    decides, and ties-to-even applies only when lo == 0. Rounding hi
    alone would round twice at those ties."""
    fmt_id = jnp.asarray(fmt_id, jnp.int32)
    t = jnp.asarray(FMT_T)[fmt_id]
    emin = jnp.asarray(FMT_EMIN)[fmt_id]
    emax = jnp.asarray(FMT_EMAX)[fmt_id]
    xmax = jnp.asarray(FMT_XMAX_BITS32)[fmt_id]
    sat = jnp.asarray(FMT_SATURATE)[fmt_id]
    hi = x.hi
    r = _chop_core(hi, t, emin, emax, xmax, sat)
    half = _chop_core(hi, t + np.int32(1), emin, emax, xmax, sat)
    tie = (half == hi) & (r != hi) & (x.lo != _ZERO)
    step = jnp.abs(hi - r)                       # exact: one t-ulp / 2
    r = jnp.where(tie, hi + jnp.where(x.lo > _ZERO, step, -step), r)
    wide = t > np.int32(24)
    return DW(jnp.where(wide, hi, r), jnp.where(wide, x.lo, _ZERO))


# -- triangular substitution --------------------------------------------------

def _pad(LU: DW, b: DW, k: int):
    """LU and b padded to a multiple of `k` with an identity tail,
    which solves to zero and never couples back."""
    n = LU.hi.shape[-1]
    npad = -(-n // k) * k
    if npad == n:
        return LU, b
    eye = jnp.eye(npad, dtype=F32)
    return (DW(eye.at[:n, :n].set(LU.hi),
               jnp.zeros_like(eye).at[:n, :n].set(LU.lo)),
            tmap(lambda a: jnp.pad(a, (0, npad - n)), b))


def _flip(x: DW) -> DW:
    """Reverse every axis: an upper triangle becomes a lower one."""
    return tmap(lambda a: jnp.flip(a), x)


def _substitute(M: DW, b: DW, unit, k: int) -> DW:
    """Forward substitution on the lower triangle of M, whose order is a
    multiple of `k`; `unit` (a bool, possibly traced) takes the diagonal
    as ones. One diagonal block of `k` unknowns per step of a
    `lax.fori_loop` (the traced program is one step's ops whatever n
    is): inside the block, unknown by unknown, y_i from the updated
    right-hand side (divided by the diagonal unless `unit`), then y_i
    times column i of the block, exact products, subtracted in DW from
    the block's unsolved entries; then the block's columns times its
    unknowns, summed by the pinned tree, subtracted from every entry
    below it. The block's own indices are static, so a step is a few
    passes on a TPU, not one per unknown."""
    npad = M.hi.shape[-1]
    idx = jnp.arange(npad)

    def step(s, carry):
        r, y = carry
        i0 = s * k
        D = tmap(lambda a: lax.dynamic_slice(a, (i0, i0), (k, k)), M)
        rb = tmap(lambda a: lax.dynamic_slice(a, (i0,), (k,)), r)
        ys = []
        for t in range(k):
            yt = tmap(lambda a: a[t], rb)
            d = tmap(lambda a: a[t, t], D)
            yt = where(unit, yt, div(yt, where(d.hi == _ZERO, lift(_ONE), d)))
            ys.append(yt)
            if t < k - 1:
                rb = where(np.arange(k) > t,
                           sub(rb, mul(tmap(lambda a: a[:, t], D),
                                       tmap(lambda a: jnp.broadcast_to(
                                           a, (k,)), yt))), rb)
        yb = DW(jnp.stack([v.hi for v in ys]), jnp.stack([v.lo for v in ys]))
        cols = tmap(lambda a: lax.dynamic_slice(a, (0, i0), (npad, k)), M)
        off = sum(mul(cols, tmap(lambda a: jnp.broadcast_to(
            a, (npad, k)), yb)), -1)
        r = where(idx >= i0 + k, sub(r, off), r)
        y = DW(lax.dynamic_update_slice(y.hi, yb.hi, (i0,)),
               lax.dynamic_update_slice(y.lo, yb.lo, (i0,)))
        return r, y

    return lax.fori_loop(0, npad // k, step, (b, zeros((npad,))))[1]


@partial(jax.jit, static_argnames=("lower", "block"))
def trisolve(LU: DW, b: DW, *, lower: bool, block: int = 8) -> DW:
    """Substitution on the combined LU matrix in DW: the strict lower
    triangle with a unit diagonal when `lower`, else the upper triangle
    with its diagonal, solved as the lower triangle of the reversed
    matrix (`_substitute`). With a block of a power of two, the pinned
    tree sums the reversed block in the same pairs, so both directions
    round alike."""
    n = LU.hi.shape[-1]
    k = min(block, n)
    LU, b = _pad(LU, b, k)
    if lower:
        y = _substitute(LU, b, True, k)
    else:
        y = _flip(_substitute(_flip(LU), _flip(b), False, k))
    return tmap(lambda a: a[:n], y)


@partial(jax.jit, static_argnames="block")
def lu_factors(LU: DW, block: int = 8) -> DW:
    """The combined LU matrix as `lu_solve` takes it: padded to a
    multiple of `block`, stacked with its reverse, (2, npad, npad)."""
    k = min(block, LU.hi.shape[-1])
    LU, _ = _pad(LU, zeros(LU.hi.shape[-1:]), k)
    return DW(jnp.stack([LU.hi, jnp.flip(LU.hi)]),
              jnp.stack([LU.lo, jnp.flip(LU.lo)]))


@partial(jax.jit, static_argnames="block")
def lu_solve(F: DW, b: DW, block: int = 8) -> DW:
    """U^-1 L^-1 b for the factors `F = lu_factors(LU, block)`: both
    substitutions run through one traced `_substitute` step, in a loop
    of two passes, the second on the reversed factors; the same
    arithmetic as `trisolve` lower, then upper, in half the program."""
    n = b.hi.shape[-1]
    npad = F.hi.shape[-1]
    k = min(block, n)
    b = tmap(lambda a: jnp.pad(a, (0, npad - n)), b)

    def solve(p, v):
        return _flip(_substitute(get(F, p), v, p == 0, k))

    return tmap(lambda a: a[:n], lax.fori_loop(0, 2, solve, b))
