"""GMRES-IR as a `TunableTask` — the paper's original workload.

A thin adapter over the existing `core.batching` fixed-shape layer:
`solve_rows` funnels through `solve_fixed_batch` (one compiled
`gmres_ir_batch` executable per size bucket) and lifts each
`SolveRecord` into the solver-agnostic `Outcome`.

The factorization/substitution hot path is size-dispatched by
`ir_cfg.blocking` (DESIGN.md §6.4): buckets at or above its threshold
(256 by default) factor with blocked LU and solve with the blocked
trisolve kernel on whichever precision backend the task was built
with — no task- or engine-level code is involved, the policy rides the
frozen config into the jit key.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from repro.core.action_space import ActionSpace
from repro.core.batching import SolveRecord, solve_fixed_batch
from repro.core.task import Outcome
from repro.data.matrices import LinearSystem
from repro.precision import DoubleWord
from repro.solvers.ir import IRConfig, dw_branches, gmres_ir_batch_lowerable
from repro.tasks.base import LinearSystemTask


def outcome_of_record(rec: SolveRecord) -> Outcome:
    """Lift a GMRES-IR `SolveRecord` into a generic `Outcome`."""
    return Outcome(status=int(rec.status), cost=float(rec.n_gmres),
                   metrics={"ferr": float(rec.ferr), "nbe": float(rec.nbe),
                            "n_outer": int(rec.n_outer),
                            "n_gmres": int(rec.n_gmres),
                            "res_norm": float(rec.res_norm)})


class GMRESIRTask(LinearSystemTask):
    name = "gmres_ir"
    inner_iter_metric = "n_gmres"

    def __init__(self, systems: Sequence[LinearSystem] = (),
                 action_space: Optional[ActionSpace] = None,
                 ir_cfg: IRConfig = IRConfig(),
                 bucket_step: int = 128, min_bucket: int = 128,
                 backend=None, executor=None, tune_blocking: bool = False):
        super().__init__(systems, action_space, bucket_step, min_bucket,
                         backend=backend, executor=executor,
                         tune_blocking=tune_blocking)
        self.ir_cfg = ir_cfg

    def solve_rows(self, rows, action_rows: Sequence[np.ndarray],
                   chunk: int) -> List[Outcome]:
        cfg = self.solver_cfg_for(self.ir_cfg, rows[0][0].shape[-1])
        recs = solve_fixed_batch([r[0] for r in rows], [r[1] for r in rows],
                                 [r[2] for r in rows], action_rows,
                                 cfg, chunk, backend=self.backend,
                                 executor=self.executor)
        return [outcome_of_record(r) for r in recs]

    def flush_tags(self, action_rows: Sequence[np.ndarray]) -> dict:
        """The `flush` span's args for these rows: on the double-word
        carrier, which DW branches the flush takes (`dw_lu`,
        `dw_gmres`), by the device predicate's own rule
        (`solvers.ir.dw_branches`); nothing on the f32 carrier."""
        if not isinstance(self.backend, DoubleWord):
            return {}
        dw_lu, dw_gmres = dw_branches(np.asarray(action_rows, np.int32))
        return {"dw_lu": bool(dw_lu), "dw_gmres": bool(dw_gmres)}

    def lowerable_for(self, n_pad: int):
        """AOT form (DESIGN.md §12): the same (cfg, backend)-keyed
        lowerable `solve_rows` dispatches through, so warmup builds the
        very executable live traffic will run."""
        return gmres_ir_batch_lowerable(
            self.solver_cfg_for(self.ir_cfg, int(n_pad)), self.backend)
