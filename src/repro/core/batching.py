"""Shared size-bucketing / padding / fixed-shape batch-solve layer.

The GMRES-IR task (`tasks.gmres_ir.GMRESIRTask`, and through it both the
offline `AutotuneEngine` and the online serving micro-batcher) funnels
solves through this module: systems are identity-padded to a size bucket
(solution preserving, see `data.matrices.pad_system`), stacked into
fixed-shape (chunk, n_pad, n_pad) batches — short batches are padded by
repeating row 0 — and executed with one `gmres_ir_batch` call. Because
every batch for a given (bucket, chunk) pair has the same shape, XLA
compiles each bucket exactly once per process, no matter how many
batches flow through it. That single-executable property extends to
the blocked factorization/substitution path: `ir_cfg.blocking`
(DESIGN.md §6.4) is part of the static config, so buckets at or above
its threshold compile the blocked LU + trisolve variant — once, with
the format ids still runtime data — and smaller buckets the strict
row-loop variant, on either precision backend.

`bucket_of` itself lives in the solver-free `core.task` module (the
engine buckets work without knowing any solver) and is re-exported here
for backward compatibility. Device placement and dispatch moved to
`core.executor` (DESIGN.md §7): `solve_fixed_batch` is now a thin shim
that stacks rows and hands the fixed-shape batch to a `SolveExecutor`
(single-device vmapped by default, mesh-sharded on request), kept for
the pre-executor call sites.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import jax
import numpy as np

from repro.core.executor import resolve_executor
from repro.core.task import bucket_of
from repro.data.matrices import LinearSystem, pad_system
from repro.obs import trace as obs_trace
from repro.solvers.ir import IRConfig, gmres_ir_batch_lowerable

__all__ = ["SolveRecord", "bucket_of", "pad_to_bucket",
           "records_from_stats", "solve_fixed_batch"]


@dataclasses.dataclass
class SolveRecord:
    """Host-side scalar outcome of one (system, action) GMRES-IR solve."""
    ferr: float
    nbe: float
    n_outer: int
    n_gmres: int
    status: int
    res_norm: float


def pad_to_bucket(system: LinearSystem, bucket_step: int = 128,
                  minimum: int = 128):
    """(A, b, x) identity-padded to the system's size bucket."""
    return pad_system(system, bucket_of(system.n, bucket_step, minimum))


def records_from_stats(stats, count: int) -> List[SolveRecord]:
    """First `count` rows of a batched SolveStats as host SolveRecords.

    The whole stats tuple comes to the host in ONE `jax.device_get`
    (six per-field transfers would mean six device->host round trips —
    and six cross-device gathers once the stats live on a mesh)."""
    ferr, nbe, n_outer, n_gmres, status, res = (
        np.asarray(f) for f in jax.device_get(tuple(stats)))
    return [SolveRecord(float(ferr[j]), float(nbe[j]), int(n_outer[j]),
                        int(n_gmres[j]), int(status[j]), float(res[j]))
            for j in range(count)]


def solve_fixed_batch(A_rows: Sequence[np.ndarray],
                      b_rows: Sequence[np.ndarray],
                      x_rows: Sequence[np.ndarray],
                      action_rows: Sequence[np.ndarray],
                      ir_cfg: IRConfig, chunk: int,
                      backend=None, executor=None) -> List[SolveRecord]:
    """One fixed-shape `gmres_ir_batch` dispatch over already-padded rows.

    All rows must share one padded size n_pad; the batch dimension is
    padded to exactly the executor's `preferred_chunk(chunk)` rows by
    repeating row 0, keeping the compiled shape constant. Returns one
    SolveRecord per *input* row (pad rows dropped). `backend` selects
    the precision backend (DESIGN.md §6); the rows are stacked in its
    `host_dtype` (`tasks.base.stack_flush`), so the Pallas backend's
    f32 cast happens on the host, the jnp backend's rows keep their
    dtype, and `DoubleWord` gets float64 rows to split. The `flush`
    span's `input_bytes` and `input_dtype` say what crosses to the
    device. `executor` selects device placement (DESIGN.md §7):
    None/"local" is the historical single-device vmapped path,
    "sharded" lays the batch over a device mesh. Buckets at or above
    `ir_cfg.blocking.min_n` run the blocked LU + trisolve hot path
    (DESIGN.md §6.4) inside the same vmapped executable.
    """
    from repro.precision import resolve_backend
    from repro.tasks.base import stack_flush
    ex = resolve_executor(executor)
    bk = resolve_backend(backend)
    # Flush-path spans and notes (DESIGN.md §8.4): no-ops unless the
    # server made its tracer current around this flush.
    A, b, x, acts, k = stack_flush(list(zip(A_rows, b_rows, x_rows)),
                                   action_rows, ex.preferred_chunk(chunk),
                                   bk)
    # The solver rides as a `LowerableCall`, which both keys the
    # dispatcher memo by computation value — every call site with equal
    # (cfg, backend) shares one executable per shape, across tasks —
    # and lets AOT warmup precompile the very executable this dispatch
    # will run (DESIGN.md §12).
    stats = ex.dispatch(gmres_ir_batch_lowerable(ir_cfg, bk),
                        (A, b, x, acts), A.shape[-1])
    with obs_trace.span("flush.fetch"):
        return records_from_stats(stats, k)
