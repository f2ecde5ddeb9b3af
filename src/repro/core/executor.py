"""Solve executors: device placement + dispatch of fixed-shape batches
(DESIGN.md §7).

Every solve the engine or the serving micro-batcher runs is a
fixed-shape stacked batch — `(chunk, n_pad, n_pad)` matrices plus their
`(chunk, n_pad)` vectors and `(chunk, k)` action rows. A `SolveExecutor`
is the one object that owns where those arrays live and how the batched
solver executable is dispatched over them:

  * `LocalExecutor` — the historical single-device vmapped path
    (extracted from `core.batching.solve_fixed_batch`): arrays go to the
    default device, one executable per size bucket.
  * `ShardedExecutor` — a `("data", "model")` `jax.sharding.Mesh`:
    batch rows are laid over the "data" axis via `NamedSharding` on the
    stacked arrays, so one engine sweep spans every device of the mesh;
    for systems of `model_min_n` and above the system (row) dimension is
    additionally laid over "model" with the same divisibility-checked
    `_fit` rule the LM substrate uses (`distributed/sharding`). The
    chunk is auto-rounded up to a multiple of the data-axis size
    (`preferred_chunk`), so the compiled shape stays bucket-stable no
    matter how many rows a flush happens to carry.

The data-axis layout dispatches through `shard_map`: every device runs
the *unpartitioned* per-shard program on its slice of the batch. This
is what makes cross-executor bit-equality constructive — the per-row
program is byte-for-byte the local one (batched == single row results
are already pinned by the backend suite), whereas letting GSPMD
partition the solver body changes reduction lowering with the program
context (measured: a mesh shard holding one row compiles a batch-1 dot
that accumulates differently). The "model"-axis layout for huge systems
IS GSPMD-partitioned (collectives inside the row are the point there)
and sits outside the bit-parity contract — see DESIGN.md §7.2.

Executors are tiny frozen dataclasses, hashing by value like
`BlockingPolicy` and the precision backends: wrapped batch callables
are memoized per (executor, computation key) — `batch_callable` — so
switching executors costs exactly one extra executable per bucket while
the format ids stay runtime data (the §3.4 invariant is untouched), and
equal-valued executors share executables. Cross-executor SolveRecord
bit-equality is asserted by `tests/test_executor.py` on a forced
8-device host mesh.

Compile-cliff control (DESIGN.md §12): solver entry points arrive as
`LowerableCall`s — the module-level jitted function plus its hashable
static kwargs, with the eager carrier coercion split out — so the
dispatchers hold a per-shape cache of AOT-compiled executables
(`lower().compile()`). Every call, cold or warmed, routes through the
same `Compiled` object for its shape; `precompile()` merely builds it
early, which is what makes warm-vs-cold bit-identity hold by
construction. The computation key is derived from the `LowerableCall`
value, so two tasks running the identical program share one dispatcher
and one executable per shape.

This module is solver-free (the engine and serving stack import it);
selection mirrors the precision backends: explicit argument >
`set_default_executor` > ``REPRO_SOLVE_EXECUTOR`` env var > ``"local"``.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import trace as obs_trace

ENV_VAR = "REPRO_SOLVE_EXECUTOR"


@dataclasses.dataclass(frozen=True)
class LowerableCall:
    """A batched solver entry point in AOT-compilable form (DESIGN.md §12).

    `jitted` is the module-level `jax.jit`-wrapped function and
    `statics` its hashable static kwargs — together they are the
    computation identity (`computation_key`): two tasks built over the
    same solver config and backend produce equal `LowerableCall`s and
    therefore share one wrapped dispatcher and one executable per
    shape, across tasks.

    `prepare` is the eager per-call coercion the plain entry point runs
    outside the jit boundary (device transfer + carrier-dtype cast). It
    must be fully determined by (jitted, statics) — it is excluded from
    equality/hash on purpose, so closure identity cannot split the
    memo.
    """
    jitted: Any
    statics: Tuple[Tuple[str, Any], ...] = ()
    prepare: Optional[Callable] = dataclasses.field(
        default=None, compare=False)

    def bind(self, arrays: Sequence) -> Tuple:
        """Apply the eager coercion: the arrays actually traced/run."""
        if self.prepare is None:
            return tuple(arrays)
        return tuple(self.prepare(*arrays))

    def __call__(self, *arrays):
        return self.jitted(*self.bind(arrays), **dict(self.statics))

    def lower(self, args: Sequence):
        """Lower against already-bound arrays (or ShapeDtypeStructs)."""
        return self.jitted.lower(*args, **dict(self.statics))


def computation_key(solve_fn: Callable, key=None):
    """Canonical memo key for a batched computation.

    An explicit `key` wins (legacy call sites). A `LowerableCall` keys
    by (jitted entry point, static kwargs) — its computation identity —
    so distinct task objects running the same program collapse onto one
    dispatcher and one executable per shape. Anything else keys by the
    callable itself."""
    if key is not None:
        return key
    if isinstance(solve_fn, LowerableCall):
        return (solve_fn.jitted, solve_fn.statics)
    return solve_fn


# Process-wide executable-build accounting (DESIGN.md §12): every
# `lower().compile()` a dispatcher runs is appended here, whether it
# came from AOT warmup or a lazy first hit. The persistent compilation
# cache can serve the *XLA* work from disk — that still counts as one
# in-process build; `repro.core.aot.cache_stats()` tracks disk
# hits/misses separately (those are what "zero fresh compiles on warm
# restart" is asserted on).
_COMPILE_LOG: List[dict] = []
_COMPILE_LOCK = threading.Lock()

_COMPILE_SECONDS_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                            30.0, 60.0, 120.0)


def executor_compile_count() -> int:
    """Executables built in-process so far (all executors)."""
    return len(_COMPILE_LOG)


def executor_compile_log() -> List[dict]:
    """Copies of the per-build records: executor, bucket, rows,
    backend, seconds, and its two parts: lower_s (Python trace plus
    lowering) and compile_s (XLA compile or persistent-cache load)."""
    with _COMPILE_LOCK:
        return [dict(r) for r in _COMPILE_LOG]


def _backend_label(solve_fn) -> str:
    if isinstance(solve_fn, LowerableCall):
        for k, v in solve_fn.statics:
            if k == "backend":
                return str(getattr(v, "name", v))
    return "unknown"


def _record_compile(executor_name: str, solve_fn, n_pad: int, rows: int,
                    lower_s: float, compile_s: float) -> None:
    seconds = lower_s + compile_s
    with _COMPILE_LOCK:
        _COMPILE_LOG.append({"executor": executor_name,
                             "bucket": int(n_pad), "rows": int(rows),
                             "backend": _backend_label(solve_fn),
                             "seconds": float(seconds),
                             "lower_s": float(lower_s),
                             "compile_s": float(compile_s)})
    # Fail-open against the process-default metrics registry
    # (DESIGN.md §8) — compile accounting must never break a solve.
    try:
        from repro.obs.metrics import default_registry
        reg = default_registry()
        reg.histogram(
            "repro_compile_seconds",
            "Wall seconds building one XLA executable (lower+compile) "
            "per size bucket and precision backend.",
            ("bucket", "backend"),
            buckets=_COMPILE_SECONDS_BUCKETS).labels(
                bucket=n_pad,
                backend=_backend_label(solve_fn)).observe(seconds)
        reg.counter(
            "repro_executor_compiles_total",
            "XLA executables built in-process by the per-shape compile "
            "cache (AOT warmup and lazy first hits both count).",
            ("executor",)).labels(executor=executor_name).inc()
    except Exception:
        pass


class SolveExecutor:
    """Interface shared by all solve executors (duck-typed; this base
    class documents the contract and hosts shared helpers)."""

    name: str = "abstract"

    # -- chunk policy ------------------------------------------------------
    def preferred_chunk(self, chunk: int, bucket: int = 0) -> int:
        """Dispatch granularity: the smallest batch size >= `chunk` this
        executor can lay out without a ragged device dimension. The
        engine sizes its fixed-shape chunks and the micro-batcher its
        flush target with this, so compiled shapes stay bucket-stable."""
        raise NotImplementedError

    # -- placement + dispatch ----------------------------------------------
    def shard(self, arrays: Sequence, n_pad: int) -> Tuple:
        """Place stacked batch arrays (leading dim = chunk) on this
        executor's devices."""
        raise NotImplementedError

    def wrap(self, solve_fn: Callable) -> Callable:
        """`(arrays, n_pad) -> result` dispatcher for `solve_fn` on this
        executor — a `_DirectDispatch` holding the per-shape compiled
        executable cache. May build jitted machinery; callers should
        reuse the wrapper (or go through `batch_callable`, which
        memoizes it) rather than re-wrapping per call."""
        return _DirectDispatch(self, solve_fn)

    def dispatch(self, solve_fn: Callable, arrays: Sequence, n_pad: int,
                 key=None):
        """Run a batched solver entry point over placed arrays.

        The wrapped dispatcher is memoized per (executor, computation
        key); `LowerableCall`s key themselves by value. Callers passing
        plain fresh lambdas MUST provide a stable `key` describing the
        computation — (entry point, config, backend) — or a sharded
        executor would rebuild (and recompile) its dispatch wrapper on
        every call."""
        from repro import faults
        faults.maybe_raise("executor.dispatch", executor=self.name,
                           n_pad=n_pad)
        # Placement, carrier coercion and the launch, up to the return
        # of the call: no sync here, the results stay on the device.
        with obs_trace.span("flush.dispatch"):
            return batch_callable(self, key, solve_fn)(arrays, n_pad)

    def precompile(self, solve_fn: Callable, arrays: Sequence,
                   n_pad: int, key=None) -> bool:
        """AOT-build the executable the first `dispatch` of these shapes
        would otherwise compile lazily (DESIGN.md §12). Goes through the
        same `batch_callable` memo, so a later live call finds both the
        wrapper and the per-shape executable warm. Returns True when an
        executable now exists for the shapes (False: no AOT form, the
        shape compiles on first hit exactly as before)."""
        wrapped = batch_callable(self, key, solve_fn)
        pre = getattr(wrapped, "precompile", None)
        if pre is None:          # custom executor with a plain closure
            return False
        return bool(pre(arrays, n_pad))

    # -- accounting --------------------------------------------------------
    def device_count(self) -> int:
        raise NotImplementedError

    def mesh_shape(self) -> Optional[Dict[str, int]]:
        """Axis-name -> size of the execution mesh (None when local)."""
        return None


@dataclasses.dataclass(frozen=True)
class LocalExecutor(SolveExecutor):
    """Single-device vmapped dispatch — the historical
    `solve_fixed_batch` behavior, now behind the executor contract."""

    name: str = dataclasses.field(default="local", init=False)

    def preferred_chunk(self, chunk: int, bucket: int = 0) -> int:
        return int(chunk)

    def shard(self, arrays, n_pad: int):
        return tuple(arrays)

    def device_count(self) -> int:
        return 1


# One Mesh per (data, model) shape per process: Mesh construction is
# cheap but identity matters for jit cache reuse across executor
# instances that hash equal.
_MESH_CACHE: Dict[Tuple[int, int], Mesh] = {}


def _mesh_for(data: int, model: int) -> Mesh:
    key = (int(data), int(model))
    if key not in _MESH_CACHE:
        devs = jax.devices()
        need = key[0] * key[1]
        if need > len(devs):
            raise ValueError(
                f"ShardedExecutor mesh ({key[0]} data x {key[1]} model) "
                f"needs {need} devices but the host exposes {len(devs)} "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "for a host-device mesh)")
        _MESH_CACHE[key] = Mesh(
            np.asarray(devs[:need]).reshape(key), ("data", "model"))
    return _MESH_CACHE[key]


@dataclasses.dataclass(frozen=True)
class ShardedExecutor(SolveExecutor):
    """Mesh dispatch: batch rows over "data", big systems over "model".

    `data=None` sizes the data axis to every device the host exposes
    (divided by `model`); an explicit `data` pins the mesh width (the
    scaling benchmark sweeps it). The data-axis layout dispatches
    through `shard_map` — each device runs the unpartitioned per-shard
    program, which is what makes it bit-identical to `LocalExecutor`
    (DESIGN.md §7.3).

    The system dimension only joins the "model" axis at `n_pad >=
    model_min_n`: below that, row-dimension collectives cost more than
    they parallelize. That path IS GSPMD-partitioned (the partitioner
    inserts the row-dimension collectives), so it sits outside the
    bit-parity contract — partitioning within a row changes reduction
    structure (DESIGN.md §7.2).
    """

    name: str = dataclasses.field(default="sharded", init=False)
    data: Optional[int] = None
    model: int = 1
    model_min_n: int = 1024

    # -- mesh --------------------------------------------------------------
    def data_size(self) -> int:
        if self.data is not None:
            return int(self.data)
        return max(1, jax.device_count() // int(self.model))

    def mesh(self) -> Mesh:
        return _mesh_for(self.data_size(), self.model)

    def device_count(self) -> int:
        return self.data_size() * int(self.model)

    def mesh_shape(self) -> Dict[str, int]:
        return {"data": self.data_size(), "model": int(self.model)}

    # -- chunk policy ------------------------------------------------------
    def preferred_chunk(self, chunk: int, bucket: int = 0) -> int:
        """Round up to a multiple of the data-axis size, so every
        device carries the same number of rows and the compiled shape
        is stable per bucket."""
        d = self.data_size()
        return max(d, -(-int(chunk) // d) * d)

    def _model_engaged(self, n_pad: int, mesh: Mesh) -> bool:
        from repro.distributed.sharding import _fit
        return (n_pad >= self.model_min_n
                and _fit(n_pad, "model", mesh) is not None)

    # -- placement ---------------------------------------------------------
    def _spec(self, shape: Tuple[int, ...], n_pad: int, mesh: Mesh) -> P:
        # Divisibility-checked axis fitting, shared with the LM
        # substrate's batch_spec rules (drop the axis rather than pad).
        from repro.distributed.sharding import _fit
        entries = [_fit(shape[0], "data", mesh)]
        entries += [None] * (len(shape) - 1)
        if len(shape) == 3 and shape[1] == n_pad \
                and self._model_engaged(n_pad, mesh):
            entries[1] = _fit(n_pad, "model", mesh)
        return P(*entries)

    def shard(self, arrays, n_pad: int):
        mesh = self.mesh()
        return tuple(
            jax.device_put(a, NamedSharding(
                mesh, self._spec(np.shape(a), n_pad, mesh)))
            for a in arrays)

    # -- dispatch ----------------------------------------------------------
    def wrap(self, solve_fn: Callable) -> Callable:
        return _MeshDispatch(self, solve_fn)


# ---------------------------------------------------------------------------
# Dispatchers: per-shape compiled-executable caches (DESIGN.md §12)
# ---------------------------------------------------------------------------


class _BatchDispatch:
    """Memoized `(arrays, n_pad) -> result` dispatcher with a per-shape
    cache of AOT-compiled executables.

    Every call — cold first hit or AOT-warmed — routes through the same
    `Compiled` object for its shapes, so warmup cannot change numerics:
    there is exactly one executable per (computation key, shapes), and
    `precompile()` merely builds it early. The lock makes the build
    safe against a background warmup thread racing a live solve."""

    def __init__(self, executor: "SolveExecutor", solve_fn: Callable):
        self.executor = executor
        self.solve_fn = solve_fn
        self.executables: Dict[tuple, Any] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _shape_key(args) -> tuple:
        return tuple(
            (tuple(int(d) for d in np.shape(a)),
             str(getattr(a, "dtype", None) or np.asarray(a).dtype))
            for a in args)

    def _lowered(self, args):
        raise NotImplementedError

    def _executable(self, args, n_pad: int):
        key = self._shape_key(args)
        exe = self.executables.get(key)
        if exe is not None:
            return exe
        with self._lock:
            exe = self.executables.get(key)
            if exe is None:
                rows = int(np.shape(args[0])[0]) if np.ndim(args[0]) else 0
                labels = dict(cat="aot", bucket=int(n_pad), rows=rows,
                              executor=self.executor.name)
                t0 = time.perf_counter()
                with obs_trace.span("aot.lower", **labels):
                    lowered = self._lowered(args)
                t1 = time.perf_counter()
                with obs_trace.span("aot.compile", **labels):
                    exe = lowered.compile()
                _record_compile(self.executor.name, self.solve_fn,
                                n_pad, rows, t1 - t0,
                                time.perf_counter() - t1)
                self.executables[key] = exe
        return exe

    def precompile(self, arrays: Sequence, n_pad: int) -> bool:
        raise NotImplementedError


class _DirectDispatch(_BatchDispatch):
    """Placement + direct dispatch (LocalExecutor, custom executors).
    `LowerableCall` solve_fns route through the per-shape compiled
    cache; plain callables keep the historical direct-call path (their
    own jit owns compilation, nothing to AOT)."""

    def _args(self, arrays, n_pad: int):
        return self.solve_fn.bind(self.executor.shard(arrays, n_pad))

    def _lowered(self, args):
        return self.solve_fn.lower(args)

    def __call__(self, arrays, n_pad: int):
        if not isinstance(self.solve_fn, LowerableCall):
            return self.solve_fn(*self.executor.shard(arrays, n_pad))
        args = self._args(arrays, n_pad)
        return self._executable(args, n_pad)(*args)

    def precompile(self, arrays, n_pad: int) -> bool:
        if not isinstance(self.solve_fn, LowerableCall):
            return False
        self._executable(self._args(arrays, n_pad), n_pad)
        return True


class _MeshDispatch(_BatchDispatch):
    """Mesh dispatch (ShardedExecutor): the data-axis shard_map program
    is jitted once per dispatcher and AOT-compiled per shape. Any
    solve_fn works — shard_map traces it — so the sharded grid
    precompiles even for plain callables. A `LowerableCall`'s eager
    coercion is traced *inside* the per-shard program, exactly where
    the plain entry point ran it before, keeping the per-shard jaxpr
    (and therefore the §7.3 bit-parity contract) unchanged. The GSPMD
    "model" path keeps the direct call: it is outside the bit-parity
    contract by design (DESIGN.md §7.2)."""

    def __init__(self, executor: "ShardedExecutor", solve_fn: Callable):
        super().__init__(executor, solve_fn)
        self._mesh = executor.mesh()
        self._d = executor.data_size()
        if isinstance(solve_fn, LowerableCall):
            jitted, prep = solve_fn.jitted, solve_fn.prepare
            statics = dict(solve_fn.statics)

            def fn(*arrays):
                bound = prep(*arrays) if prep is not None else arrays
                return jitted(*bound, **statics)
        else:
            fn = solve_fn
        self._fn = fn
        mesh = self._mesh

        @jax.jit
        def data_sharded(*arrays):
            in_specs = tuple(P("data", *([None] * (a.ndim - 1)))
                             for a in arrays)
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=P("data"),
                                 check_vma=False)(*arrays)

        self._jit = data_sharded   # compile-accounting hook for tests

    def _lowered(self, args):
        return self._jit.lower(*args)

    def _placed(self, arrays, n_pad: int):
        chunk = np.shape(arrays[0])[0]
        if chunk % self._d:
            raise ValueError(
                f"batch of {chunk} rows does not divide over the "
                f"{self._d}-wide data axis; size batches with "
                "preferred_chunk()")
        return self.executor.shard(arrays, n_pad)

    def __call__(self, arrays, n_pad: int):
        placed = self._placed(arrays, n_pad)
        if self.executor._model_engaged(n_pad, self._mesh):
            # Huge systems: GSPMD lays rows over "model" and partitions
            # the solver body (collectives inside the row). Outside the
            # bit-parity contract by design.
            return self._fn(*placed)
        return self._executable(placed, n_pad)(*placed)

    def precompile(self, arrays, n_pad: int) -> bool:
        placed = self._placed(arrays, n_pad)
        if self.executor._model_engaged(n_pad, self._mesh):
            return False       # the model path compiles via its own jit
        self._executable(placed, n_pad)
        return True


# ---------------------------------------------------------------------------
# Wrapped-callable memo
# ---------------------------------------------------------------------------

# (executor, computation key) -> wrapped batch dispatcher. Executors
# are frozen value-hashed dataclasses, so equal executors share
# dispatchers (and therefore compiled executables). `LowerableCall`s
# key by value — (jitted entry point, statics) — which is what dedupes
# executable builds across tasks running the same program; plain
# callers must pass a stable explicit key.
_WRAPPED: Dict[tuple, Callable] = {}
_WRAPPED_LOCK = threading.RLock()


def batch_callable(executor: "SolveExecutor", key,
                   solve_fn: Callable) -> Callable:
    """Memoized `executor.wrap(solve_fn)`, keyed by `computation_key`.

    The first `solve_fn` registered for (executor, key) wins; callers
    passing fresh lambdas must ensure equal keys imply identical
    computations. Thread-safe: a background AOT warmup sweep and a live
    solve may race to build the same wrapper (DESIGN.md §12)."""
    k = (executor, computation_key(solve_fn, key))
    with _WRAPPED_LOCK:
        if k not in _WRAPPED:
            _WRAPPED[k] = executor.wrap(solve_fn)
            # A memo miss means a new dispatcher: at least one new
            # executable per (executor, computation key). Fail-open
            # against the process-default registry (DESIGN.md §8).
            try:
                from repro.obs.metrics import default_registry
                default_registry().counter(
                    "repro_executor_wrap_builds_total",
                    "Wrapped batch dispatchers built — one per "
                    "(executor, computation key).",
                    ("executor",)).labels(executor=executor.name).inc()
            except Exception:
                pass
        return _WRAPPED[k]


# ---------------------------------------------------------------------------
# Registry + selection (mirrors precision.backend)
# ---------------------------------------------------------------------------

ExecutorLike = Union[None, str, SolveExecutor]

_REGISTRY: Dict[str, Callable[[], SolveExecutor]] = {
    "local": LocalExecutor,
    "sharded": ShardedExecutor,
}
_DEFAULT: Optional[SolveExecutor] = None


def register_executor(name: str,
                      factory: Callable[[], SolveExecutor]) -> None:
    """Register an executor factory under `name` (overwrites allowed)."""
    _REGISTRY[name] = factory


def available_executors():
    return sorted(_REGISTRY)


def _from_name(name: str) -> SolveExecutor:
    if name not in _REGISTRY:
        raise KeyError(f"unknown solve executor {name!r}; "
                       f"available: {available_executors()}")
    return _REGISTRY[name]()


def set_default_executor(executor: ExecutorLike) -> Optional[SolveExecutor]:
    """Set the process-wide default executor (None restores env/'local'
    resolution). Returns the previous override, for save/restore."""
    global _DEFAULT
    prev = _DEFAULT
    _DEFAULT = (resolve_executor(executor)
                if executor is not None else None)
    return prev


def default_executor() -> SolveExecutor:
    if _DEFAULT is not None:
        return _DEFAULT
    return _from_name(os.environ.get(ENV_VAR, "local"))


def resolve_executor(executor: ExecutorLike = None) -> SolveExecutor:
    """Coerce an executor spec (instance | name | None=default) into an
    executor instance. Pure Python — safe to call before tracing."""
    if executor is None:
        return default_executor()
    if isinstance(executor, str):
        return _from_name(executor)
    return executor
