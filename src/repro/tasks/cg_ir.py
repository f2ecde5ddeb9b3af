"""CG-IR as a `TunableTask` — proof the autotuning API generalizes.

Same bandit, same engine, same server as GMRES-IR; only the batched
solver and the work metric differ. Intended for SPD systems (the
`data.matrices.sparse_spd` generator); on indefinite matrices the CG
recurrence breaks down and the reward's failure path takes over.

As with GMRES-IR, `cg_cfg.blocking` (DESIGN.md §6.4) size-dispatches
the LU preconditioner construction and its per-iteration triangular
applications onto the blocked hot path for buckets at or above the
threshold.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.core.action_space import ActionSpace
from repro.core.task import Outcome
from repro.data.matrices import LinearSystem
from repro.obs import trace as obs_trace
from repro.solvers.cg import CGConfig, cg_ir_batch_lowerable
from repro.tasks.base import LinearSystemTask, stack_flush


class CGIRTask(LinearSystemTask):
    name = "cg_ir"
    inner_iter_metric = "n_cg"

    def __init__(self, systems: Sequence[LinearSystem] = (),
                 action_space: Optional[ActionSpace] = None,
                 cg_cfg: CGConfig = CGConfig(),
                 bucket_step: int = 128, min_bucket: int = 128,
                 backend=None, executor=None, tune_blocking: bool = False):
        super().__init__(systems, action_space, bucket_step, min_bucket,
                         backend=backend, executor=executor,
                         tune_blocking=tune_blocking)
        self.cg_cfg = cg_cfg

    def solve_rows(self, rows, action_rows: Sequence[np.ndarray],
                   chunk: int) -> List[Outcome]:
        A, b, x, acts, k = stack_flush(
            rows, action_rows, self.executor.preferred_chunk(chunk),
            self.backend)
        cfg = self.solver_cfg_for(self.cg_cfg, A.shape[-1])
        # Value-keyed lowerable: dedupes the executable with any other
        # call site (or task) running the same (cfg, backend) program
        # and gives AOT warmup its precompile target (DESIGN.md §12).
        stats = self.executor.dispatch(
            cg_ir_batch_lowerable(cfg, self.backend),
            (A, b, x, acts), A.shape[-1])
        # One host transfer for the whole stats tuple (DESIGN.md §7).
        with obs_trace.span("flush.fetch"):
            ferr, nbe, n_outer, n_cg, status, res = (
                np.asarray(f) for f in jax.device_get(tuple(stats)))
        return [Outcome(status=int(status[j]), cost=float(n_cg[j]),
                        metrics={"ferr": float(ferr[j]),
                                 "nbe": float(nbe[j]),
                                 "n_outer": int(n_outer[j]),
                                 "n_cg": int(n_cg[j]),
                                 "res_norm": float(res[j])})
                for j in range(k)]

    def lowerable_for(self, n_pad: int):
        """AOT form (DESIGN.md §12): same (cfg, backend)-keyed lowerable
        as `solve_rows`, so warmup and live traffic share executables."""
        return cg_ir_batch_lowerable(
            self.solver_cfg_for(self.cg_cfg, int(n_pad)), self.backend)
