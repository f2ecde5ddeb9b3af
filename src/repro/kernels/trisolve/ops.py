"""Jitted public wrapper for the trisolve kernel: padding, SMEM params
and the batching rule that runs a vmapped call's systems in one launch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.chop.ops import make_fmt_params

from .ref import pad_unit, trisolve_ref
from .trisolve import MAX_N, trisolve_pallas


def _record_systems(bucket: int, systems: int) -> None:
    """Fail-open gauge against the process-default metrics registry
    (DESIGN.md §8): how many systems one launch solves side by side, set
    when the kernel is traced into a program."""
    try:
        from repro.obs.metrics import default_registry
        default_registry().gauge(
            "repro_trisolve_systems_per_launch",
            "Systems one trisolve kernel launch solves side by side, one "
            "per sublane, as last traced per size bucket.",
            ("bucket",)).labels(bucket=bucket).set(systems)
    except Exception:
        pass


@functools.lru_cache(maxsize=None)
def _solve_systems(lower: bool, block: int, interpret: bool):
    """The kernel over a leading axis of systems, (B, n, n) x (B, n) x
    int32[B, P] -> (B, n), with a `custom_vmap` rule that folds a vmapped
    axis into that leading axis — nested vmaps included — instead of
    letting the pallas_call batching rule run the systems one grid step
    after another."""

    @jax.custom_batching.custom_vmap
    def solve(Lu, b, fmt):
        n = Lu.shape[-1]
        n_pad = -(-n // block) * block
        Lp, bp = jax.vmap(functools.partial(pad_unit, n_pad=n_pad))(Lu, b)
        _record_systems(n, Lu.shape[0])
        out = trisolve_pallas(Lp, bp, fmt, lower=lower, block=block,
                              interpret=interpret)
        return out[:, :n]

    @solve.def_vmap
    def _fold(axis_size, in_batched, Lu, b, fmt):
        args = [x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, batched in zip((Lu, b, fmt), in_batched)]
        out = solve(*(x.reshape((-1,) + x.shape[2:]) for x in args))
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return solve


def trisolve_op(Lu: jnp.ndarray, b: jnp.ndarray, fmt_id, *,
                lower: bool, block: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """Blocked triangular solve on the combined LU matrix, f32 carrier.

    Identity-pads n to the block multiple shared with `ref.trisolve_ref`
    (padded shapes and reduction lengths are part of the bit-exactness
    contract, DESIGN.md §6.2) and runs the single-launch kernel. Under
    `jax.vmap` every system of the call runs in that one launch, one per
    sublane (`trisolve` module docstring); an unbatched call is the same
    kernel on one system. Systems larger than `trisolve.MAX_N` exceed
    the whole-matrix VMEM budget and route to the bit-identical oracle —
    a pure perf choice, like the pallas backend's `chop_min_elems`
    routing. `interpret=True` runs the Pallas interpreter (CPU tests).
    """
    if Lu.dtype != jnp.float32 or b.dtype != jnp.float32:
        raise TypeError("trisolve_op targets the f32 TPU carrier; got "
                        f"{Lu.dtype} x {b.dtype}")
    n = Lu.shape[-1]
    n_pad = -(-n // block) * block
    if n_pad > MAX_N:
        return trisolve_ref(Lu, b, fmt_id, lower=lower, block=block)
    solve = _solve_systems(lower, block, interpret)
    return solve(Lu[None], b[None], make_fmt_params(fmt_id))[0]
