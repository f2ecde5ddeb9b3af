"""Shared `TunableTask` implementation for linear-system solvers.

Both shipped tasks (GMRES-IR, CG-IR) autotune per-step precisions for
`Ax = b` over `data.matrices.LinearSystem` instances, so everything but
the batched solver itself lives here: paper features (Eq. 18), size
bucketing with identity padding (solution preserving), fixed-shape
batch stacking, and the Eq. 21 reward mapped from an `Outcome`'s
metrics. Subclasses provide `name`, `inner_iter_metric` (the metrics
key holding the work count fed to the Eq. 25 penalty), and
`solve_rows`.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.action_space import ActionSpace
from repro.core.executor import resolve_executor
from repro.core.features import PAPER_FEATURES, feature_vector
from repro.core.rewards import reward as reward_fn
from repro.core.task import Outcome, bucket_of
from repro.data.matrices import LinearSystem, pad_system
from repro.obs import trace as obs_trace
from repro.precision import resolve_backend


def stack_fixed(rows: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
                action_rows: Sequence[np.ndarray], chunk: int,
                dtype: Optional[str] = None):
    """Stack padded (A, b, x) rows + action rows into fixed-shape arrays.

    The batch dimension is padded to exactly `chunk` by repeating row 0,
    keeping the compiled shape constant; callers drop the pad rows from
    the results (`k` = number of real rows).

    `dtype` is the float dtype A, b and x are stacked in, in the same
    numpy pass (None = the rows' own). Callers pass their backend's
    `host_dtype`: float32 on the Pallas backend, so its carrier cast
    (round to nearest even, as XLA's convert) happens here and no f64
    array crosses to the device; float64 on `DoubleWord`, whose
    `prepare` splits the rows into (hi, lo); None on the jnp backend.
    """
    k = len(rows)
    assert 0 < k <= chunk, (k, chunk)
    idx = list(range(k)) + [0] * (chunk - k)
    A = np.stack([rows[i][0] for i in idx], dtype=dtype)
    b = np.stack([rows[i][1] for i in idx], dtype=dtype)
    x = np.stack([rows[i][2] for i in idx], dtype=dtype)
    acts = np.stack([np.asarray(action_rows[i], np.int32) for i in idx])
    return A, b, x, acts, k


def stack_flush(rows, action_rows: Sequence[np.ndarray], chunk: int,
                backend):
    """A live flush's `stack_fixed`, in `backend.host_dtype`, timed as
    the `flush.stack` span. The `flush` span then notes what crosses to
    the device: `input_bytes` (A, b, x and actions, pad rows in) and
    `input_dtype` (the stacked A's dtype)."""
    with obs_trace.span("flush.stack"):
        A, b, x, acts, k = stack_fixed(rows, action_rows, chunk,
                                       dtype=backend.host_dtype)
    obs_trace.note("flush", input_bytes=int(
        A.nbytes + b.nbytes + x.nbytes + acts.nbytes),
        input_dtype=str(A.dtype))
    return A, b, x, acts, k


class LinearSystemTask:
    """Base task over a (possibly empty) set of `LinearSystem`s.

    `action_space` may be None for serving-only adapters; the server
    injects the promoted policy snapshot's space before any reward is
    computed.

    `backend` selects the precision backend the batched solver runs on
    (DESIGN.md §6): an instance, a registry name ("jnp", "pallas", ...),
    or None for the process default. It is resolved once here so every
    solve the engine/server funnels through this task hits the same
    compiled executable.

    `executor` selects the solve executor the same way (DESIGN.md §7):
    an instance, a registry name ("local", "sharded"), or None for the
    process default. The executor owns device placement and chunk
    granularity; the engine and micro-batcher read it off the task.

    `tune_blocking=True` runs a one-off startup sweep per (bucket,
    backend) over blocked-LU panel widths and pins the winner into that
    bucket's solver config (`solvers.block_autotune`) — the same
    measure-then-commit move the bandit makes for precisions, applied
    to the kernel-blocking knob. Off by default: the tuned policy is a
    legitimate config change (panel-restricted pivoting differs by
    width), so opting in is a per-task decision.
    """

    name = "linear-system"
    inner_iter_metric = "n_inner"

    def __init__(self, systems: Sequence[LinearSystem] = (),
                 action_space: Optional[ActionSpace] = None,
                 bucket_step: int = 128, min_bucket: int = 128,
                 backend=None, executor=None, tune_blocking: bool = False):
        self.instances: List[LinearSystem] = list(systems)
        self.action_space = action_space
        self.bucket_step = bucket_step
        self.min_bucket = min_bucket
        self.backend = resolve_backend(backend)
        self.executor = resolve_executor(executor)
        self.tune_blocking = tune_blocking
        self._features: Optional[np.ndarray] = None
        self._kappas: Optional[np.ndarray] = None
        self._tuned_cfgs: dict = {}

    # -- context features --------------------------------------------------
    @property
    def features(self) -> np.ndarray:
        if self._features is None:
            if not self.instances:
                return np.zeros((0, len(PAPER_FEATURES)))
            self._features = np.stack([self.feature_of(s)
                                       for s in self.instances])
        return self._features

    @property
    def kappas(self) -> np.ndarray:
        if self._kappas is None:
            self._kappas = np.array([s.features["kappa_est"]
                                     for s in self.instances])
        return self._kappas

    def feature_of(self, system: LinearSystem) -> np.ndarray:
        return feature_vector(system.features)

    @property
    def carrier(self) -> str:
        """The label of the carrier this task's solves run on."""
        return self.backend.carrier

    # -- shape bucketing ---------------------------------------------------
    def bucket_key(self, system: LinearSystem) -> int:
        return bucket_of(system.n, self.bucket_step, self.min_bucket)

    def prepare(self, system: LinearSystem):
        """(A, b, x) identity-padded to the system's size bucket."""
        return pad_system(system, self.bucket_key(system))

    # -- solving / reward --------------------------------------------------
    def solver_cfg_for(self, cfg, n_pad: int):
        """Per-bucket solver config: the static config, with the
        blocked-LU panel width swapped for the startup-sweep winner when
        `tune_blocking` is on. Cached per (config type, bucket), so each
        bucket still compiles exactly one executable."""
        if not self.tune_blocking:
            return cfg
        key = (type(cfg).__name__, int(n_pad))
        if key not in self._tuned_cfgs:
            from repro.solvers.block_autotune import tuned_blocking
            pol = tuned_blocking(n_pad, backend=self.backend,
                                 base=cfg.blocking)
            self._tuned_cfgs[key] = (
                cfg if pol == cfg.blocking
                else dataclasses.replace(cfg, blocking=pol))
        return self._tuned_cfgs[key]

    def solve_rows(self, rows, action_rows, chunk: int) -> List[Outcome]:
        raise NotImplementedError

    # -- AOT warmup (DESIGN.md §12) ----------------------------------------
    def lowerable_for(self, n_pad: int):
        """The batched solver as a `core.executor.LowerableCall` for one
        padded size, or None when the task has no AOT form (warmup then
        falls back to first-hit compilation, exactly as before)."""
        return None

    def warm_rows(self, bucket: int):
        """One representative prepared row for `bucket`: an identity
        system with the exact shapes/dtypes of any live padded row
        (`data.matrices.pad_system` pads with the identity, so this is
        literally a member of the live input family)."""
        n = int(bucket)
        return (np.eye(n), np.ones(n), np.ones(n))

    def precompile_bucket(self, bucket: int, chunk: int) -> bool:
        """AOT-build this task's executable for (bucket, chunk) without
        solving anything (DESIGN.md §12). The warm batch is shaped
        exactly like a live flush — `stack_fixed` in the backend's host
        dtype to the executor's preferred chunk, int32 action rows — so
        the first real request hits the compiled executable. Returns
        False when the task has no AOT form."""
        low = self.lowerable_for(int(bucket))
        if low is None or self.action_space is None:
            return False
        row = self.warm_rows(int(bucket))
        action = np.asarray(self.action_space.actions[0], np.int32)
        A, b, x, acts, _ = stack_fixed(
            [row], [action],
            self.executor.preferred_chunk(int(chunk), int(bucket)),
            dtype=self.backend.host_dtype)
        return bool(self.executor.precompile(low, (A, b, x, acts),
                                             A.shape[-1]))

    def reward(self, outcome: Outcome, action_idx: int,
               instance: LinearSystem, cfg) -> float:
        """Eq. 21 on the outcome's metrics; the inner-iteration count
        named by `inner_iter_metric` feeds the Eq. 25 work penalty."""
        m = outcome.metrics
        return reward_fn(m["ferr"], m["nbe"], m[self.inner_iter_metric],
                         outcome.status,
                         self.action_space.actions[int(action_idx)],
                         instance.features["kappa_est"], cfg)
