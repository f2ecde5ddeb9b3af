"""The in-process entry: `AutotuneServer.submit`/`step`, the README's
quickstart path, driven as a closed loop.

Set-up builds the server as a deployment does (the configuration's
policy snapshot, task, batcher and online settings, the platform's
default precision backend and executor, sync AOT warmup over the
configuration's buckets), generates the pool, and sends one batch per
bucket through the server so that every program the window runs has
run once. The window keeps `outstanding` requests in flight: each
answer frees a slot that the next pool system, in the run's seeded
order, fills at once. After the window closes, what is still queued is
flushed and waited for; those answers count in the latency tail and not
in the window's throughput."""
import os
import time

import numpy as np

import bench
import trace_reduce


def _annotate(on: bool, name: str):
    import contextlib
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _counters(registry) -> dict:
    """Window-delta inputs: solver and padded rows, and the solve-batch
    histogram's sum and count, summed over labels."""
    out = {"solver_rows": 0.0, "padded_rows": 0.0, "solve_batch_sum": 0.0,
           "solve_batch_count": 0.0, "solver_batches": 0.0}
    names = {"repro_service_solver_rows_total": "solver_rows",
             "repro_service_padded_rows_total": "padded_rows",
             "repro_service_solver_batches_total": "solver_batches"}
    for fam in registry.collect():
        for _, child in fam.samples():
            if fam.name in names:
                out[names[fam.name]] += child.value
            elif fam.name == "repro_service_solve_batch_seconds":
                out["solve_batch_sum"] += child.sum
                out["solve_batch_count"] += child.count
    return out


def build(cell: dict, seed: int, backend=None, features: bool = True):
    """(server, task, systems, pool): everything set-up makes. The
    precision backend is the platform's default unless one is given.
    Without `features` no systems are made (the HTTP front door builds
    them from its requests) and the result is (server, task, pool)."""
    from repro.core import PrecisionPolicy
    from repro.core.features import system_features
    from repro.core.rewards import RewardConfig
    from repro.data.matrices import LinearSystem
    from repro.obs import MetricsRegistry, Observability
    from repro.service import AutotuneServer, BatcherConfig, OnlineConfig

    cfg, tr = cell["config_file"], cell["traffic_file"]
    pool = bench.make_pool(cfg, int(tr["pool"]))
    systems = [LinearSystem(p["A"], p["b"], p["x_true"], p["kappa"],
                            system_features(p["A"]), "bench")
               for p in pool] if features else None
    policy = PrecisionPolicy.load(os.path.join(bench.HERE, cfg["policy"]))
    task = bench.module("tasks", cfg["task"]).build(cfg, backend=backend)
    b = cfg["batcher"]
    srv = AutotuneServer(
        policy, task, reward_cfg=RewardConfig(**cfg["reward"]),
        batcher_cfg=BatcherConfig(max_batch=b["max_batch"],
                                  max_wait_s=b["max_wait_s"],
                                  bucket_step=b["bucket_step"],
                                  min_bucket=b["min_bucket"]),
        online_cfg=OnlineConfig(**cfg["online"]),
        clock=time.perf_counter, seed=seed % 2 ** 32,
        obs=Observability(registry=MetricsRegistry(),
                          trace_capacity=1 << 20),
        warmup="sync", warmup_buckets=list(cfg["buckets"]))
    return (srv, task, systems, pool) if features else (srv, task, pool)


def request_order(count: int, seed: int):
    """Pool indices in the run's order: passes over the whole pool, each
    in a fresh permutation drawn from the seed."""
    rng = np.random.default_rng(seed)
    while True:
        for i in rng.permutation(count):
            yield int(i)


def run(cell: dict, seed: int, seconds: float, trace_dir=None,
        trace_s: float = 3.0, build_fn=build) -> dict:
    """Set-up, the window and the drain; returns the run's record."""
    import jax
    from repro.core import aot, executor_compile_count
    from jax._src import dispatch

    t_setup0 = time.perf_counter()
    srv, task, systems, pool = build_fn(cell, seed)
    cfg, tr = cell["config_file"], cell["traffic_file"]
    inner_key = bench.module("tasks", cfg["task"]).INNER_METRIC
    done = []
    srv.on_response = lambda r: done.append((r, time.perf_counter()))

    # One batch per bucket through the live path, so that every program
    # the window runs (solver executables, host-side conversions, the
    # reward and Q-update) has run once before it opens.
    by_bucket = {}
    for i, s in enumerate(systems):
        by_bucket.setdefault(task.bucket_key(s), []).append(i)
    for bucket in sorted(by_bucket):
        for i in by_bucket[bucket][:cfg["batcher"]["max_batch"]]:
            srv.submit(systems[i])
    srv.drain()
    for r, _ in done:
        srv.poll(r.request_id)
    done.clear()

    compiles = {"backend": 0}

    def on_event(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            compiles["backend"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    exe0 = executor_compile_count()
    order = request_order(len(systems), seed)
    sent = {}
    answers = []
    traced = trace_dir is not None
    c0 = _counters(srv.obs.registry)
    spans0 = len(srv.obs.tracer)
    t_start = time.perf_counter()
    t_end = t_start + seconds
    trace_win = None

    def submit():
        i = next(order)
        t = time.perf_counter()
        with _annotate(traced, "bench.submit"):
            rid = srv.submit(systems[i])
        sent[rid] = (i, t)

    def collect(refill: bool):
        # Only the answers that are in now: a refill that fills a bucket
        # flushes it at once, and its answers wait for the next turn.
        batch = done[:]
        done.clear()
        for r, t in batch:
            i, ts = sent.pop(r.request_id)
            srv.poll(r.request_id)
            m = r.record.metrics
            answers.append({
                "i": i, "n": pool[i]["n"], "bucket": int(r.bucket),
                "action": int(r.action),
                "action_names": list(r.action_names),
                "status": int(r.record.status),
                "expired": bool(r.expired),
                "ferr": float(m.get("ferr", np.inf)),
                "nbe": float(m.get("nbe", np.inf)),
                "inner": int(m.get(inner_key, 0)),
                "t_submit": ts, "t_done": t, "rid": int(r.request_id)})
            if refill and time.perf_counter() < t_end:
                submit()

    for _ in range(int(tr["outstanding"])):
        submit()
    while True:
        collect(refill=True)
        now = time.perf_counter()
        if now >= t_end:
            break
        if traced and trace_win is None and now >= t_end - trace_s:
            # The window's last `trace_s` seconds; the trace stops after
            # the window closes, where writing it holds up no request.
            jax.profiler.start_trace(trace_dir)
            mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            mark.__enter__()
            trace_win = [time.perf_counter(), None]
        with _annotate(traced, "bench.step"):
            srv.step()
        if not done:
            time.sleep(0.0005)
    if trace_win is not None:
        mark.__exit__(None, None, None)
        trace_win[1] = time.perf_counter()
        jax.profiler.stop_trace()
    c1 = _counters(srv.obs.registry)
    window_compiles = (executor_compile_count() - exe0, compiles["backend"])
    # After the close: flush what is queued, wait for every answer.
    deadline = time.perf_counter() + 60.0
    while sent and time.perf_counter() < deadline:
        srv.drain()
        collect(refill=False)
    spans = [s for s in srv.obs.tracer.spans()[spans0:]]
    return {
        "t_setup0": t_setup0, "t_start": t_start, "t_end": t_end,
        "seconds": seconds, "answers": answers,
        "unanswered": len(sent), "counters": (c0, c1),
        "spans": [(s.name, s.t0, s.t1, s.tid, dict(s.args or {}))
                  for s in spans],
        "window_compiles": window_compiles,
        "warmup_s": float(srv.warmup.seconds),
        "warmup_errors": list(srv.warmup.errors),
        "cache": aot.cache_stats(), "trace_window": trace_win,
        "pool": pool, "server": srv,
        "max_batch": int(cfg["batcher"]["max_batch"]),
    }
