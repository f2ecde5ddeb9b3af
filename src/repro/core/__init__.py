"""The paper's primary contribution: contextual-bandit precision autotuning.

Layered solver-agnostically around the `TunableTask` API:

  * `task.py` — the `TunableTask` protocol + `Outcome` (what an
    algorithm must expose to be autotuned); concrete tasks live in
    `repro.tasks` (GMRES-IR, CG-IR).
  * `engine.py` — `AutotuneEngine`: the single learning loop (solve
    cache, epsilon-greedy selection, Q-updates) shared by offline
    training and the online service.
  * `autotune.py` — Alg. 3 `train_policy` / `evaluate_policy` drivers
    over any task or engine.
  * Framework pieces: action space (Eq. 11-12), discretizer (Eq. 19-20),
    rewards (Eq. 21-25), tabular bandit (Eq. 5-6), policy persistence,
    and the fixed-shape batching layer.
  * `env.py` — the deprecated `GMRESIREnv` shim (engine + GMRES-IR task
    fused, kept for pre-TunableTask call sites).
"""
from . import aot
from .action_space import (ActionSpace, fp8_reduced_action_space,
                           full_action_space, is_monotone,
                           reduced_action_space, reduced_size)
from .autotune import (TrainConfig, TrainHistory, as_engine,
                       evaluate_fixed_action, evaluate_policy, train_policy)
from .bandit import QTable, epsilon_schedule
from .batching import (SolveRecord, bucket_of, pad_to_bucket,
                       records_from_stats, solve_fixed_batch)
from .discretize import Discretizer
from .engine import AutotuneEngine
from .env import GMRESIREnv
from .executor import (LocalExecutor, LowerableCall, ShardedExecutor,
                       SolveExecutor, computation_key,
                       executor_compile_count, executor_compile_log,
                       resolve_executor)
from .policy import PrecisionPolicy
from .rewards import (RewardConfig, W1, W2, accuracy_term, penalty_term,
                      precision_term, reward, reward_batch)
from .task import (CONVERGED, FAILED, MAXITER, STAGNATED, Outcome,
                   TunableTask, coerce_task, is_tunable_task)

__all__ = [
    "ActionSpace", "fp8_reduced_action_space", "full_action_space",
    "is_monotone", "reduced_action_space", "reduced_size",
    "SolveExecutor", "LocalExecutor", "LowerableCall", "ShardedExecutor",
    "resolve_executor", "aot",
    "computation_key", "executor_compile_count", "executor_compile_log",
    "TrainConfig", "TrainHistory",
    "as_engine", "evaluate_fixed_action", "evaluate_policy", "train_policy",
    "QTable", "epsilon_schedule", "Discretizer", "AutotuneEngine",
    "GMRESIREnv", "SolveRecord", "bucket_of", "pad_to_bucket",
    "records_from_stats", "solve_fixed_batch", "PrecisionPolicy",
    "RewardConfig", "W1", "W2", "accuracy_term", "penalty_term",
    "precision_term", "reward", "reward_batch", "Outcome", "TunableTask",
    "coerce_task", "is_tunable_task", "CONVERGED", "STAGNATED", "MAXITER",
    "FAILED",
]
