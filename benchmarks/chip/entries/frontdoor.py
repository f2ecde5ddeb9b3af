"""The HTTP entry: `POST /v1/solve:sync` through the program's
`serve_http` front door, driven as an open loop by a child process
(entries/frontdoor_client.py) that imports no JAX.

Set-up builds the server as the in-process entry does, opens the front
door with `HttpConfig(max_n=<largest bucket>)` and its other settings
at their defaults, and lets the child encode the pool's request bodies
meanwhile. The child warms the front door with one request per bucket,
then sends each request at its due time; latency is from the due time
to the answer, so a late send counts against the server. After the
window the child waits up to a minute for the answers still out."""
import json
import os
import queue
import subprocess
import sys
import threading
import time

import bench
import trace_reduce

inproc = bench.module("entries", "inproc")

CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "frontdoor_client.py")


def build(cell: dict, seed: int, backend=None):
    """(server, task, pool): the in-process entry's server, with no
    systems made: the front door builds each from its request."""
    return inproc.build(cell, seed, backend=backend, features=False)


def run(cell: dict, seed: int, seconds: float, trace_dir=None,
        trace_s: float = 3.0, build_fn=build, rate=None) -> dict:
    import jax
    from jax._src import dispatch
    from repro.core import aot, executor_compile_count
    from repro.service.http import HttpConfig, serve_http

    t_setup0 = time.perf_counter()
    cfg = cell["config_file"]
    cmd = [sys.executable, CLIENT, "--seed", str(seed), "--seconds",
           str(seconds)]
    if rate is not None:
        cmd += ["--rate", str(rate)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE, text=True, env=env)
    lines = queue.Queue()
    reader = threading.Thread(
        target=lambda: [lines.put(json.loads(ln)) for ln in child.stdout]
        + [lines.put(None)], daemon=True)
    reader.start()
    child.stdin.write(json.dumps({"config": cfg,
                                  "traffic": cell["traffic_file"]}) + "\n")
    child.stdin.flush()
    fd = None
    try:
        srv, task, pool = build_fn(cell, seed)
        fd = serve_http(srv, cfg=HttpConfig(max_n=max(cfg["buckets"])))
        ready = lines.get(timeout=600)
        if not ready or ready.get("event") != "ready":
            raise RuntimeError(f"load generator failed: {ready!r}")
        child.stdin.write(f"go {fd.url}\n")
        child.stdin.flush()
        start = lines.get(timeout=600)
        if not start or start.get("event") != "start":
            raise RuntimeError(f"load generator failed: {start!r}")
        compiles = {"backend": 0}

        def on_event(event, duration, **kw):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                compiles["backend"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        exe0 = executor_compile_count()
        c0 = inproc._counters(srv.obs.registry)
        spans0 = len(srv.obs.tracer)
        t_start = float(start["t"])
        t_end = t_start + seconds
        trace_win = None
        if trace_dir is not None:
            time.sleep(max(0.0, t_end - trace_s - time.perf_counter()))
            jax.profiler.start_trace(trace_dir)
            mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            mark.__enter__()
            trace_win = [time.perf_counter(), None]
        time.sleep(max(0.0, t_end - time.perf_counter()))
        if trace_win is not None:
            mark.__exit__(None, None, None)
            trace_win[1] = time.perf_counter()
            jax.profiler.stop_trace()
        c1 = inproc._counters(srv.obs.registry)
        window_compiles = (executor_compile_count() - exe0,
                           compiles["backend"])
        got = []
        while True:
            ev = lines.get(timeout=120)
            if ev is None or ev["event"] == "end":
                break
            got.append(ev)
        n_due = int(ready["requests"])
        spans = srv.obs.tracer.spans()[spans0:]
    finally:
        if fd is not None:
            fd.close()
        child.stdin.close()
        child.wait(timeout=120)
    answers = []
    for ev in got:
        out = ev["outcome"] or {}
        ok = ev["code"] == 200 and ev["status"] == "done"
        answers.append({
            "i": ev["i"], "n": pool[ev["i"]]["n"], "bucket": ev["bucket"],
            "action": ev["action"], "action_names": ev["action_names"],
            "status": int(out["status"]) if ok else 3,
            "expired": ev["status"] == "expired", "code": ev["code"],
            "ferr": float(out["ferr"]) if ok else float("inf"),
            "nbe": float(out["nbe"]) if ok else float("inf"),
            "inner": int(out.get("n_gmres") or out.get("n_cg") or 0),
            "t_submit": ev["due"], "t_sent": ev["sent"],
            "t_done": ev["done"], "latency_s": ev["latency_s"],
            "rid": ev["rid"]})
    return {
        "t_setup0": t_setup0, "t_start": t_start, "t_end": t_end,
        "seconds": seconds, "answers": answers,
        "unanswered": n_due - len(answers), "counters": (c0, c1),
        "spans": [(s.name, s.t0, s.t1, s.tid, dict(s.args or {}))
                  for s in spans],
        "window_compiles": window_compiles,
        "warmup_s": float(srv.warmup.seconds),
        "warmup_errors": list(srv.warmup.errors),
        "cache": aot.cache_stats(), "trace_window": trace_win,
        "pool": pool, "server": srv,
        "max_batch": int(cfg["batcher"]["max_batch"]),
    }
