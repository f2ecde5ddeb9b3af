"""Ahead-of-time executable-grid warmup + persistent compile cache
(DESIGN.md §12).

The serving stack compiles one XLA executable per (computation key,
bucket, chunk, backend, executor) grid cell. Left lazy, every cell is
paid as a first-hit latency cliff — minutes of cold start before the
first solve, and a p99 outlier on every new shape, which poisons
exactly the time signal the bandit's reward is built on. This module
kills the cliff three ways:

  * `plan()` + `precompile()` — enumerate the grid for a set of tasks
    and AOT-build it through the exact per-shape compile caches the
    live path dispatches from (`core.executor`). Warm hits are
    bit-identical to cold ones by construction: both run the same
    `Compiled` object.
  * `BackgroundWarmup` — the same sweep on a daemon thread, priority
    ordered (most-traffic bucket first, smallest first among ties;
    traffic read from a trajectory log when one exists), so the
    likeliest buckets go warm first and the server's `/readyz`
    warm-bucket gate flips per bucket as each cell lands.
  * `enable_persistent_cache()` — jax's persistent compilation cache,
    in ``JAX_COMPILATION_CACHE_DIR`` when that is set and otherwise at
    the fixed ``<checkout>/.cache/xla``: restarts reuse compiles from
    disk, with hit/miss events mirrored into `repro.obs` counters so
    "the warm restart did zero fresh XLA compiles" is a counter
    assertion, not a timing guess. This also makes the §11 crash
    recovery path fast, not just correct.
"""
from __future__ import annotations

import contextvars
import dataclasses
import json
import os
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

from repro.obs import trace as obs_trace

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_cache_dir: Optional[str] = None
_cache_events = {"hits": 0, "misses": 0}
_listener_installed = False


def _count(name: str, help: str, amount: float = 1.0, **labels) -> None:
    """Fail-open counter against the process-default metrics registry
    (DESIGN.md §8) — warmup accounting must never take a server down."""
    try:
        from repro.obs.metrics import default_registry
        fam = default_registry().counter(name, help,
                                         tuple(sorted(labels)))
        (fam.labels(**labels) if labels else fam).inc(amount)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Persistent compilation cache (cross-process compile reuse)
# ---------------------------------------------------------------------------


def default_cache_dir() -> str:
    """The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset:
    a fixed path inside the checkout. It must not move between runs —
    a restart finds its compiles again only at the same path."""
    return str(Path(__file__).resolve().parents[3] / ".cache" / "xla")


def enable_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache and return the
    directory in force. ``JAX_COMPILATION_CACHE_DIR``, when set, is that
    directory — jax reads it itself and nothing here overrides it;
    otherwise `default_cache_dir()`. Idempotent.

    The size/time thresholds are dropped to zero: the repro's grid is
    many small executables — exactly the entries jax's defaults decline
    to persist — and the whole point is that a restarted server
    rebuilds its grid from disk instead of re-running XLA."""
    global _cache_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from_env = os.environ.get(ENV_CACHE_DIR)
    d = from_env or default_cache_dir()
    if _cache_dir == d:
        return d
    if not from_env:
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # A compile that ran before this point initialised the cache without
    # these settings; start it afresh so they hold from here on.
    compilation_cache.reset_cache()
    _install_listener()
    _cache_dir = d
    return d


def _install_listener() -> None:
    """Mirror jax's compilation-cache hit/miss monitoring events into
    counters. This is the counter-based warm-restart signal: a restart
    whose grid is fully served from disk records zero misses."""
    global _listener_installed
    if _listener_installed:
        return
    try:
        from jax import monitoring

        def _on_event(event, *args, **kwargs):
            if event.endswith("/cache_hits"):
                _cache_events["hits"] += 1
                _count("repro_compile_cache_hits_total",
                       "Persistent-compilation-cache hits (XLA compile "
                       "served from the cache directory).")
            elif event.endswith("/cache_misses"):
                _cache_events["misses"] += 1
                _count("repro_compile_cache_misses_total",
                       "Persistent-compilation-cache misses (fresh XLA "
                       "compilation, result written to disk).")

        monitoring.register_event_listener(_on_event)
        _listener_installed = True
    except Exception:
        pass


def cache_stats() -> dict:
    """Persistent-cache state: directory in force (None = disabled),
    hit/miss event counts since process start, and the process totals
    of the executable builds' two timers (`core.executor`): `lower_s`,
    Python trace plus lowering, and `compile_s`, XLA compile or
    persistent-cache load."""
    from repro.core.executor import executor_compile_log
    log = executor_compile_log()
    return {"dir": _cache_dir, "hits": int(_cache_events["hits"]),
            "misses": int(_cache_events["misses"]),
            "lower_s": sum(r["lower_s"] for r in log),
            "compile_s": sum(r["compile_s"] for r in log)}


# ---------------------------------------------------------------------------
# Grid enumeration + priority order
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GridEntry:
    """One cell of the executable grid: (task, bucket) at the serving
    chunk. Precision backend and executor ride on the task; identical
    programs across tasks collapse onto one executable inside
    `core.executor` (`computation_key`), so over-enumerating is safe."""
    task: object
    bucket: int
    chunk: int

    def labels(self) -> dict:
        return {"task": getattr(self.task, "name", "unknown"),
                "bucket": int(self.bucket),
                "backend": str(getattr(
                    getattr(self.task, "backend", None), "name",
                    "unknown")),
                "executor": str(getattr(
                    getattr(self.task, "executor", None), "name",
                    "unknown"))}


def bucket_traffic(trajectory_path: Optional[str]) -> Dict[int, int]:
    """Per-bucket request counts from a JSONL trajectory log
    (`obs.trajlog` format; fail-open — unreadable path or rows yield
    {}). This is what makes warmup priority follow production traffic
    across restarts: the log survives the process, the jit caches
    don't."""
    counts: Dict[int, int] = {}
    if not trajectory_path:
        return counts
    try:
        with open(trajectory_path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    b = json.loads(line).get("bucket")
                except Exception:
                    continue
                if b is not None:
                    counts[int(b)] = counts.get(int(b), 0) + 1
    except OSError:
        return counts
    return counts


def order_buckets(buckets: Sequence[int],
                  traffic: Optional[Dict[int, int]] = None,
                  trajectory_path: Optional[str] = None) -> List[int]:
    """Warmup priority: most-seen bucket first (explicit `traffic`
    counts plus trajectory-log counts), smallest first among ties —
    small buckets compile fastest, so the grid starts flipping the
    `/readyz` gate as early as possible."""
    counts: Dict[int, int] = {int(b): int(c)
                              for b, c in (traffic or {}).items()}
    for b, c in bucket_traffic(trajectory_path).items():
        counts[b] = counts.get(b, 0) + c
    return sorted({int(b) for b in buckets},
                  key=lambda b: (-counts.get(b, 0), b))


def plan(tasks: Sequence, buckets: Sequence[int], chunk: int,
         traffic: Optional[Dict[int, int]] = None,
         trajectory_path: Optional[str] = None) -> List[GridEntry]:
    """Enumerate the executable grid in warmup-priority order: every
    task for the hottest bucket, then the next bucket, and so on."""
    ordered = order_buckets(buckets, traffic, trajectory_path)
    return [GridEntry(task, int(b), int(chunk))
            for b in ordered for task in tasks]


# ---------------------------------------------------------------------------
# Warmup sweeps
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class WarmupReport:
    """Outcome of one warmup sweep. `warmed`/`skipped` hold bucket keys
    in completion order (skipped = the task had no AOT form for the
    cell; it will compile on first hit exactly as before)."""
    entries: int = 0
    warmed: List[int] = dataclasses.field(default_factory=list)
    skipped: List[int] = dataclasses.field(default_factory=list)
    errors: List[str] = dataclasses.field(default_factory=list)
    seconds: float = 0.0
    done: bool = False


def _sweep(entries: Sequence[GridEntry], report: WarmupReport,
           on_entry: Optional[Callable], pace: Optional[Callable]
           ) -> WarmupReport:
    t0 = time.perf_counter()
    for e in entries:
        if pace is not None:
            pace(e)
        try:
            # Parent of the build's aot.lower / aot.compile spans
            # (core.executor), in the server's tracer when it set one.
            with obs_trace.span("aot.bucket", cat="aot",
                                tid=int(e.bucket), **e.labels()):
                ok = bool(e.task.precompile_bucket(e.bucket, e.chunk))
        except Exception as err:
            # Fail-open by contract: warmup must never take a server
            # down — the cell just compiles lazily on first hit.
            ok = False
            report.errors.append(f"bucket {e.bucket}: {err!r}")
        (report.warmed if ok else report.skipped).append(int(e.bucket))
        _count("repro_warmup_buckets_total",
               "Executable-grid cells processed by AOT warmup.",
               task=e.labels()["task"],
               status="warmed" if ok else "skipped")
        report.seconds = time.perf_counter() - t0
        if on_entry is not None:
            try:
                on_entry(e, ok)
            except Exception:
                pass
    report.done = True
    return report


def precompile(entries: Sequence[GridEntry],
               on_entry: Optional[Callable] = None) -> WarmupReport:
    """Run the grid eagerly (the server's ``warmup="sync"`` path).
    `on_entry(entry, warmed)` fires after each cell — the server flips
    its per-bucket `/readyz` warm gate there."""
    return _sweep(entries, WarmupReport(entries=len(entries)),
                  on_entry, None)


class BackgroundWarmup:
    """`precompile()` on a daemon thread (``warmup="background"``):
    priority-ordered cells land one by one, flipping per-bucket state
    through `on_entry` while the server is already accepting traffic.

    `pace` (optional) is called with each entry *before* it compiles —
    a rate-limiting / sequencing hook: production can yield the CPU to
    serving threads between cells, and tests step the sweep
    deterministically. The per-shape locks in `core.executor` make a
    live solve racing the warmup of the same cell safe: one of them
    builds, both use the same executable."""

    def __init__(self, entries: Sequence[GridEntry],
                 on_entry: Optional[Callable] = None,
                 pace: Optional[Callable] = None):
        self.entries = list(entries)
        self.report = WarmupReport(entries=len(self.entries))
        self._on_entry = on_entry
        self._pace = pace
        # The sweep runs in the constructing context, so it records
        # into the tracer that was current there.
        self._context = contextvars.copy_context()
        self._thread = threading.Thread(
            target=self._run, name="repro-aot-warmup", daemon=True)

    def start(self) -> "BackgroundWarmup":
        self._thread.start()
        return self

    def _run(self) -> None:
        self._context.run(_sweep, self.entries, self.report,
                          self._on_entry, self._pace)

    @property
    def done(self) -> bool:
        return self.report.done

    def wait(self, timeout: Optional[float] = None) -> WarmupReport:
        self._thread.join(timeout)
        return self.report
