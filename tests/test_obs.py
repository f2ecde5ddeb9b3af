"""Observability layer: fail-open metrics, Prometheus exposition, the
HTTP front door, request tracing, and the trajectory log.

The load-bearing test here is the fault-injection one: a server whose
sinks / tracer / trajectory log all raise must produce bit-identical
responses to a server with observability disabled — instrumentation can
never change a solve result or drop a response (DESIGN.md §8.1)."""
import json
import os
import shutil
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import GMRESIREnv, TrainConfig, W1, reduced_action_space
from repro.obs import (MetricsRegistry, Observability, Tracer,
                       TrajectoryLog, default_registry, fail_open,
                       lint_exposition, render_json, render_prometheus)
from repro.service import (AutotuneServer, BatcherConfig, OnlineConfig,
                           PolicyRegistry, Telemetry)
from repro.data import generate_dense_set
from repro.solvers import IRConfig

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SPACE = reduced_action_space()
IR = IRConfig(tau=1e-6)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# Metrics registry: fail-open mutators, sinks, exposition
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("repro_t_requests_total", "Requests.", ("task",))
    c.labels(task="a").inc()
    c.labels(task="a").inc(2)
    c.labels(task="b").inc(0.5)
    assert c.labels(task="a").value == pytest.approx(3.0)
    assert c.labels(task="b").value == pytest.approx(0.5)

    g = reg.gauge("repro_t_pending", "Pending.")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.labels().value == pytest.approx(3.0)

    h = reg.histogram("repro_t_wait_seconds", "Wait.", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    child = h.labels()
    assert child.count == 3
    assert child.sum == pytest.approx(5.55)
    assert child.cumulative() == [1, 2, 3]     # le=0.1, le=1, +Inf
    assert reg.errors == 0

    # Families are get-or-create: same name returns the same object...
    assert reg.counter("repro_t_requests_total", "", ("task",)) is c
    # ...but re-registering with different labels is a hard error (a
    # programming bug, caught at construction, not on the hot path).
    with pytest.raises(ValueError):
        reg.counter("repro_t_requests_total", "", ("other",))


def test_metric_mutators_are_fail_open():
    reg = MetricsRegistry()
    c = reg.counter("repro_t_x_total", "X.")
    c.inc(5)
    c.inc(-1)                      # negative increment: rejected, counted
    c.inc(float("nan"))            # non-finite: rejected, counted
    assert c.labels().value == pytest.approx(5.0)
    assert reg.errors == 2

    g = reg.gauge("repro_t_g", "G.")
    g.set("not-a-number")          # ValueError swallowed
    assert g.labels().value == 0.0
    assert reg.errors == 3

    # Wrong label names raise *outside* the guard (facades reach labels()
    # only through fail_open-wrapped methods).
    with pytest.raises(ValueError):
        reg.counter("repro_t_lab_total", "", ("task",)).labels(wrong="x")


def test_raising_sink_is_counted_not_propagated():
    reg = MetricsRegistry()
    seen = []

    def bad_sink(name, labels, value):
        raise RuntimeError("exporter down")

    reg.add_sink(bad_sink)
    reg.add_sink(lambda name, labels, value: seen.append((name, value)))
    c = reg.counter("repro_t_sink_total", "S.")
    c.inc()
    c.inc()
    # The raising sink never reaches the caller, is counted per sample,
    # and does not starve the healthy sink registered after it.
    assert c.labels().value == 2.0
    assert reg.errors == 2
    assert seen == [("repro_t_sink_total", 1.0), ("repro_t_sink_total", 2.0)]


def test_fail_open_decorator_counts_and_returns_none():
    reg = MetricsRegistry()

    class Facade:
        def __init__(self):
            self.registry = reg

        @fail_open
        def boom(self):
            raise RuntimeError("instrumentation bug")

        @fail_open
        def ok(self):
            return 42

    f = Facade()
    assert f.boom() is None
    assert f.ok() == 42
    assert reg.errors == 1


def test_default_registry_is_a_process_singleton():
    assert default_registry() is default_registry()
    assert Observability().registry is default_registry()
    assert Observability(registry=MetricsRegistry()).registry \
        is not default_registry()


def test_prometheus_exposition_golden_format():
    reg = MetricsRegistry()
    reg.gauge("repro_demo_pending", "Pending.").set(2)
    reg.counter("repro_demo_requests_total", "Demo requests.",
                ("task",)).labels(task="gmres").inc(3)
    h = reg.histogram("repro_demo_wait_seconds", "Wait.", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    assert render_prometheus(reg) == (
        "# HELP repro_demo_pending Pending.\n"
        "# TYPE repro_demo_pending gauge\n"
        "repro_demo_pending 2\n"
        "# HELP repro_demo_requests_total Demo requests.\n"
        "# TYPE repro_demo_requests_total counter\n"
        'repro_demo_requests_total{task="gmres"} 3\n'
        "# HELP repro_demo_wait_seconds Wait.\n"
        "# TYPE repro_demo_wait_seconds histogram\n"
        'repro_demo_wait_seconds_bucket{le="0.1"} 1\n'
        'repro_demo_wait_seconds_bucket{le="1"} 1\n'
        'repro_demo_wait_seconds_bucket{le="+Inf"} 2\n'
        "repro_demo_wait_seconds_sum 5.05\n"
        "repro_demo_wait_seconds_count 2\n"
        "# HELP repro_obs_errors_total Instrumentation exceptions "
        "swallowed by the fail-open guard.\n"
        "# TYPE repro_obs_errors_total counter\n"
        "repro_obs_errors_total 0\n")
    assert lint_exposition(render_prometheus(reg)) == []
    js = render_json(reg)
    assert js["repro_demo_requests_total"]["samples"][0] == {
        "labels": {"task": "gmres"}, "value": 3.0}
    assert js["repro_demo_wait_seconds"]["samples"][0]["count"] == 2


def test_exposition_lint_catches_violations():
    bad = (
        "# TYPE bad_metric counter\n"
        "bad_metric 1\n"
        "# TYPE repro_foo counter\n"
        "repro_foo 2\n"
        "# TYPE repro_request_latency histogram\n"
        'repro_request_latency_bucket{le="+Inf"} 1\n'
        "repro_request_latency_sum 1\n"
        "repro_request_latency_count 1\n"
        'repro_thing{BadLabel="x"} 1\n')
    problems = "\n".join(lint_exposition(bad))
    assert "bad_metric" in problems and "repro_" in problems
    assert "repro_foo" in problems and "_total" in problems
    assert "repro_request_latency" in problems and "_seconds" in problems
    assert "BadLabel" in problems


# ---------------------------------------------------------------------------
# Tracer + trajectory log (unit)
# ---------------------------------------------------------------------------

def test_tracer_ring_is_bounded_and_filterable():
    tr = Tracer(capacity=4)
    for i in range(6):
        tr.add_span("s", t0=float(i), t1=float(i) + 0.5, tid=i % 2)
    assert len(tr) == 4                       # ring kept the most recent
    assert [s.t0 for s in tr.spans()] == [2.0, 3.0, 4.0, 5.0]
    assert all(s.tid == 1 for s in tr.spans(tid=1))
    ev = tr.chrome_trace()["traceEvents"]
    assert len(ev) == 4
    assert ev[0] == {"name": "s", "cat": "request", "ph": "X",
                     "ts": 2e6, "dur": 0.5e6, "pid": 0, "tid": 0}


def test_tracer_span_contextmanager_nests(tmp_path):
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", tid=7):
        clock.advance(1.0)
        with tr.span("inner", tid=7, detail="x"):
            clock.advance(2.0)
        clock.advance(1.0)
    inner, outer = tr.spans()                 # inner closes first
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert inner.duration == pytest.approx(2.0)
    assert outer.duration == pytest.approx(4.0)
    assert inner.args == {"detail": "x"}
    path = tmp_path / "trace.json"
    tr.dump(str(path))
    with open(path) as f:
        assert len(json.load(f)["traceEvents"]) == 2


def test_trajectory_log_roundtrip_and_corruption_tolerance(tmp_path):
    path = str(tmp_path / "traj.jsonl")
    with TrajectoryLog(path) as log:
        log.append({"task": "a", "reward": np.float64(1.5),
                    "features": [np.float32(2.0)], "request_id": 0})
        log.append({"task": "b", "reward": 2.0, "request_id": 1})
        assert log.written == 2
    # Simulate a torn tail write of a crashed server.
    with open(path, "a") as f:
        f.write('{"task": "c", "rew')
    recs = TrajectoryLog.read(path)
    assert len(recs) == 2                     # corrupt tail skipped
    assert recs[0]["reward"] == 1.5           # numpy scalars -> floats
    assert recs[0]["features"] == [2.0]
    assert TrajectoryLog.read(path, task="b") == [
        {"task": "b", "reward": 2.0, "request_id": 1}]


def test_trajectory_log_rotates_on_size(tmp_path):
    path = str(tmp_path / "traj.jsonl")
    with TrajectoryLog(path, max_bytes=200, max_segments=2) as log:
        for i in range(40):
            log.append({"request_id": i, "task": "t"})
        assert log.rotations >= 2
    segs = TrajectoryLog.segments(path)
    assert segs[-1] == path                   # active file is newest
    assert len(segs) <= 3                     # .2, .1 + active
    for seg in segs:                          # bounded: limit + 1 record
        assert os.path.getsize(seg) <= 200 + 64
    # Readers span the live segments oldest-first: ids stay ordered, the
    # newest record survives, the oldest were rotated out and deleted.
    ids = [r["request_id"] for r in TrajectoryLog.read(path)]
    assert ids == sorted(ids)
    assert ids[-1] == 39
    assert 0 < len(ids) < 40


def test_trajectory_log_without_limit_never_rotates(tmp_path):
    path = str(tmp_path / "traj.jsonl")
    with TrajectoryLog(path) as log:
        for i in range(200):
            log.append({"request_id": i})
        assert log.rotations == 0
    assert TrajectoryLog.segments(path) == [path]
    assert len(TrajectoryLog.read(path)) == 200


def test_trajectory_log_truncation_at_segment_boundary(tmp_path):
    """A rotated segment whose tail was torn mid-record (crash during
    rotation, disk-full) loses exactly that record: the reader keeps
    every complete line in that segment and everything in the segments
    around it."""
    path = str(tmp_path / "traj.jsonl")
    with TrajectoryLog(path, max_bytes=120, max_segments=4) as log:
        for i in range(20):
            log.append({"request_id": i, "task": "t"})
        assert log.rotations >= 2
    segs = TrajectoryLog.segments(path)
    assert len(segs) >= 3
    victim = segs[1]                           # a middle rotated segment
    before = [json.loads(ln) for ln in open(victim) if ln.strip()]
    with open(victim, "rb+") as f:
        f.truncate(os.path.getsize(victim) - 7)   # tear the last record
    recs = list(TrajectoryLog.iter_records(path))
    ids = [r["request_id"] for r in recs]
    assert before[-1]["request_id"] not in ids    # torn record dropped
    for r in before[:-1]:                         # rest of segment kept
        assert r["request_id"] in ids
    assert ids == sorted(ids)                     # ordering undisturbed


def test_trajectory_log_iter_records_ordering_across_segments(tmp_path):
    """iter_records yields exactly the surviving append order — oldest
    rotated segment first, active file last, no interleaving."""
    path = str(tmp_path / "traj.jsonl")
    with TrajectoryLog(path, max_bytes=150, max_segments=3) as log:
        for i in range(30):
            log.append({"request_id": i})
    per_seg = [[json.loads(ln)["request_id"] for ln in open(seg)
                if ln.strip()]
               for seg in TrajectoryLog.segments(path)]
    flat = [i for seg in per_seg for i in seg]
    assert [r["request_id"]
            for r in TrajectoryLog.iter_records(path)] == flat
    assert flat == sorted(flat)                # oldest-first, contiguous
    assert flat[-1] == 29


def test_trajectory_log_append_after_rotation_keeps_ordering(tmp_path):
    """Appends after a rotation land in the fresh active file and read
    back *after* everything in the rotated segments, even across a
    writer reopen."""
    path = str(tmp_path / "traj.jsonl")
    log = TrajectoryLog(path, max_bytes=120, max_segments=3)
    for i in range(12):
        log.append({"request_id": i})
    assert log.rotations >= 1
    rotated_at = log.rotations
    log.append({"request_id": 100})            # post-rotation append
    log.close()
    # A new writer on the same path appends to the active file, not a
    # fresh segment.
    with TrajectoryLog(path, max_bytes=10**6, max_segments=3) as log2:
        log2.append({"request_id": 101})
        assert log2.rotations == 0
    ids = [r["request_id"] for r in TrajectoryLog.iter_records(path)]
    assert ids[-2:] == [100, 101]
    assert ids == sorted(ids)
    assert rotated_at >= 1


def test_trajectory_log_read_complete_filters_foreign_rows(tmp_path):
    """`read_complete` keeps only rows carrying the full OPE schema, so
    decision-trail events sharing a log file never reach the
    estimators."""
    path = str(tmp_path / "traj.jsonl")
    full = {f: 0 for f in TrajectoryLog.FIELDS}
    full.update(task="t", request_id=1)
    with TrajectoryLog(path) as log:
        log.append(full)
        log.append({"event": "ope_gate", "outcome": "ope_reject",
                    "task": "t"})              # trail event, same task
        log.append(dict(full, request_id=2))
    recs = TrajectoryLog.read_complete(path, task="t")
    assert [r["request_id"] for r in recs] == [1, 2]
    # Narrower field sets widen the net.
    assert len(TrajectoryLog.read_complete(
        path, task="t", fields=("task",))) == 3


# ---------------------------------------------------------------------------
# Telemetry satellites: throughput anchor, per-bucket reservoirs
# ---------------------------------------------------------------------------

def test_throughput_window_is_anchored_at_first_submit():
    tel = Telemetry()
    tel.on_submit(16, now=10.0)
    tel.on_response(2.0, ("fp32",), 0, 1.0, now=12.0, bucket=16)
    # One response over the [first submit, last response] window: 1/2 s.
    # The old first-response anchor reported 0 for exactly this case.
    assert tel.throughput_rps == pytest.approx(0.5)
    tel.on_response(1.0, ("fp32",), 0, 1.0, now=14.0, bucket=16)
    assert tel.throughput_rps == pytest.approx(2 / 4.0)
    assert tel.snapshot()["throughput_rps"] == pytest.approx(0.5)


def test_per_bucket_latency_reservoirs():
    tel = Telemetry(max_bucket_latency_samples=4)
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        tel.on_response(v, (), 0, 0.0, now=v, bucket=16)
    tel.on_response(10.0, (), 0, 0.0, now=6.0, bucket=32)
    pb = tel.latency_percentiles_per_bucket()
    assert set(pb) == {16, 32}
    # Bounded reservoir: bucket 16 kept the most recent 4 samples.
    assert pb[16]["p50"] == pytest.approx(3.5)
    assert pb[32]["p99"] == pytest.approx(10.0)
    snap = tel.snapshot()
    assert snap["latency_s_per_bucket"][16]["p99"] == pytest.approx(
        np.percentile([2.0, 3.0, 4.0, 5.0], 99))


def test_backend_fallback_is_counted():
    """No downgrade is left to count: off a TPU, asking for the compiled
    'pallas' backend raises, and no fallback counter is registered."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("pallas is the real fast path on TPU; no fallback")
    from repro.precision.backend import resolve_backend
    with pytest.raises(RuntimeError, match="TPU"):
        resolve_backend("pallas")
    names = {f.name for f in default_registry().collect()}
    assert "repro_backend_fallbacks_total" not in names


# ---------------------------------------------------------------------------
# Service integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def warm_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("obsreg") / "reg")
    rng = np.random.default_rng(7)
    train = generate_dense_set(6, rng, n_range=(12, 28),
                               log10_kappa_range=(1, 6))
    env = GMRESIREnv(train, SPACE, IR, chunk=4, bucket_step=16)
    PolicyRegistry.warm_start(root, env, W1, TrainConfig(episodes=2))
    return root


def _server(root, obs, clock=None, seed=0):
    return AutotuneServer(
        PolicyRegistry(root), IR, W1,
        BatcherConfig(max_batch=4, max_wait_s=0.005,
                      bucket_step=16, min_bucket=16),
        OnlineConfig(), clock=clock or time.monotonic, seed=seed, obs=obs)


def _requests(n, seed, n_range=(12, 28)):
    rng = np.random.default_rng(seed)
    return generate_dense_set(n, rng, n_range, log10_kappa_range=(1, 6))


class _BrokenTracer(Tracer):
    def add_span(self, *a, **k):
        raise RuntimeError("tracer down")


class _BrokenLog:
    def append(self, record):
        raise OSError("disk full")

    def close(self):
        pass


def test_injected_obs_faults_never_change_solve_results(warm_root):
    """The acceptance property of the whole layer (DESIGN.md §8.1): a
    server whose exporter sink, tracer, or trajectory log raises on
    every call returns byte-for-byte the same responses as one with
    observability disabled — and reports the faults it swallowed."""
    reqs = _requests(8, seed=3)

    def run(obs):
        srv = _server(warm_root, obs, clock=FakeClock(), seed=0)
        ids = [srv.submit(s) for s in reqs]
        srv.drain()
        out = [srv.poll(i) for i in ids]
        assert srv.pending == 0 and all(r is not None for r in out)
        return out

    base = run(False)                          # observability disabled

    reg_a = MetricsRegistry()
    reg_a.add_sink(lambda *a: (_ for _ in ()).throw(RuntimeError("sink")))
    broken_sink_and_tracer = Observability(registry=reg_a,
                                           tracer=_BrokenTracer())
    got_a = run(broken_sink_and_tracer)

    reg_b = MetricsRegistry()
    broken_trajlog = Observability(registry=reg_b)
    broken_trajlog.trajlog = _BrokenLog()
    got_b = run(broken_trajlog)

    for got, reg in ((got_a, reg_a), (got_b, reg_b)):
        for r, b in zip(got, base):
            assert r.request_id == b.request_id
            assert r.action == b.action and r.state == b.state
            assert r.bucket == b.bucket
            assert r.reward == b.reward        # exact, not approx
            assert r.eps == b.eps and r.drift == b.drift
            assert int(r.record.status) == int(b.record.status)
            assert float(r.record.cost) == float(b.record.cost)
        assert reg.errors > 0                  # faults were accounted


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read().decode(), \
                resp.headers.get("Content-Type", "")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), e.headers.get("Content-Type", "")


def test_http_front_door_live_scrape(warm_root):
    srv = _server(warm_root, Observability(registry=MetricsRegistry()))
    http = srv.serve_obs()
    try:
        assert srv.serve_obs() is http         # idempotent

        code, body, _ = _get(http.url + "/healthz")
        health = json.loads(body)
        assert code == 200 and health["status"] == "ok"
        # Degradation surface (DESIGN.md §11.2) rides on /healthz: a
        # fresh server has no open breakers and nothing quarantined.
        assert health["open_buckets"] == [] and health["breakers"] == {}
        assert health["quarantined_updates"] == 0
        assert health["expired_requests"] == 0

        # Unready until the bucket grid is warm (nothing flushed yet).
        code, body, _ = _get(http.url + "/readyz")
        assert code == 503 and json.loads(body)["status"] == "unready"

        for s in _requests(4, seed=5, n_range=(12, 14)):   # one bucket
            srv.submit(s)
        srv.drain()
        code, body, _ = _get(http.url + "/readyz")
        assert code == 200 and json.loads(body)["status"] == "ready"

        # /metrics: Prometheus text format, convention-clean, and the
        # serving families are present with real samples.
        code, text, ctype = _get(http.url + "/metrics")
        assert code == 200 and ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        assert lint_exposition(text) == []
        assert 'repro_service_requests_total{task="gmres_ir",bucket="16"} 4' \
            in text
        assert "repro_service_request_latency_seconds_bucket" in text
        assert "repro_obs_errors_total 0" in text
        assert 'repro_obs_scrapes_total{path="/readyz"} 2' in text

        code, body, ctype = _get(http.url + "/metrics.json")
        assert code == 200 and ctype.startswith("application/json")
        js = json.loads(body)
        assert js["repro_service_responses_total"]["type"] == "counter"

        code, body, _ = _get(http.url + "/telemetry")
        assert code == 200 and json.loads(body)["responses"] == 4

        code, body, _ = _get(http.url + "/trace")
        assert code == 200 and json.loads(body)["traceEvents"]

        code, body, _ = _get(http.url + "/nope")
        assert code == 404 and json.loads(body)["error"] == "not found"
    finally:
        srv.obs.close()


def test_request_spans_order_and_trajectory_log_roundtrip(warm_root,
                                                          tmp_path):
    path = str(tmp_path / "traj.jsonl")
    obs = Observability(registry=MetricsRegistry(), trajectory_path=path)
    srv = _server(warm_root, obs)
    reqs = _requests(8, seed=9)
    ids = [srv.submit(s) for s in reqs]
    srv.drain()
    resp = {i: srv.poll(i) for i in ids}

    # Six spans per request, chained contiguously inside the envelope:
    # submit -> queue_wait -> solve -> reward -> q_update.
    for rid in ids:
        spans = {s.name: s for s in obs.tracer.spans(tid=rid)}
        assert set(spans) == {"request", "submit", "queue_wait", "solve",
                              "reward", "q_update"}
        for s in spans.values():
            assert s.t1 >= s.t0
        assert spans["request"].t0 == spans["submit"].t0
        assert spans["submit"].t1 == spans["queue_wait"].t0
        assert spans["queue_wait"].t1 == spans["solve"].t0
        assert spans["solve"].t1 == spans["reward"].t0
        assert spans["reward"].t1 == spans["q_update"].t0
        assert spans["q_update"].t1 == pytest.approx(spans["request"].t1)
        assert spans["solve"].args["n_rows"] >= 1
        assert spans["request"].args["action"] == resp[rid].action

    # Trajectory log: one record per response, full schema, matching
    # the polled values.
    obs.close()
    recs = TrajectoryLog.read(path)
    assert len(recs) == len(ids)
    by_id = {r["request_id"]: r for r in recs}
    for i in ids:
        rec, r = by_id[i], resp[i]
        assert set(TrajectoryLog.FIELDS) <= set(rec)
        assert rec["action"] == r.action and rec["state"] == r.state
        assert rec["reward"] == pytest.approx(r.reward)
        assert rec["bucket"] == r.bucket
        assert isinstance(rec["explore"], bool)
        assert 0.0 <= rec["eps"] <= 1.0
        assert rec["policy_version"] == r.policy_version
        assert all(isinstance(x, float) for x in rec["features"])
        assert rec["outcome"]["status"] == int(r.record.status)


def test_snapshot_embeds_telemetry_evidence(warm_root, tmp_path):
    root = str(tmp_path / "reg")
    shutil.copytree(warm_root, root)           # keep the shared fixture
    srv = _server(root, Observability(registry=MetricsRegistry()))
    for s in _requests(4, seed=11, n_range=(12, 14)):
        srv.submit(s)
    srv.drain()
    version = srv.snapshot()
    tel = srv.registry.meta(version)["telemetry"]
    assert tel["responses"] == 4
    assert {"reward_ewma", "abs_rpe_ewma", "drift_events",
            "throughput_rps", "latency_s",
            "latency_s_per_bucket"} <= set(tel)
    assert tel["throughput_rps"] > 0
    assert {"p50", "p90", "p99"} <= set(tel["latency_s"])
    # JSON round-trip stringifies bucket keys; the one bucket is 16.
    (bucket,) = tel["latency_s_per_bucket"]
    assert int(bucket) == 16
    assert tel["latency_s_per_bucket"][bucket]["p99"] >= 0
    text = render_prometheus(srv.obs.registry)
    assert 'repro_service_snapshots_total{task="gmres_ir"} 1' in text


# ---------------------------------------------------------------------------
# Inline spans: parent links, the current tracer, the profiler hook
# ---------------------------------------------------------------------------

def test_inline_spans_link_parents_and_inherit_rows():
    from repro.obs import trace
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with trace.span("orphan"):                 # no current tracer: no-op
        pass
    trace.note("outer", lost=1)                # nothing open: no-op
    with trace.use(tr):
        assert trace.current() is tr
        with trace.span("outer", tid=5, cat="flush", a=1) as outer:
            with trace.span("inner") as inner:
                clock.advance(1.0)
                trace.note("outer", late=2)
    assert trace.current() is None and len(tr) == 2
    by = {s.name: s for s in tr.spans()}
    assert by["outer"].sid == outer and by["inner"].sid == inner
    assert by["outer"].parent is None and by["inner"].parent == outer
    assert (by["inner"].tid, by["inner"].cat) == (5, "flush")
    assert by["outer"].args == {"a": 1, "late": 2}
    assert tr.spans(tid=5) == []               # rows are per category
    assert [s.name for s in tr.spans(tid=5, cat="flush")] == \
        ["inner", "outer"]
    ev = {e["name"]: e for e in tr.chrome_trace()["traceEvents"]}
    assert ev["inner"]["args"] == {"sid": inner, "parent": outer}


def _flush_run(root, obs, n, seed=21):
    """`n` requests of one bucket through a server, drained: a forced
    flush for n < 4, a full one at submit for n = 4."""
    srv = _server(root, obs, seed=0)
    reqs = _requests(n, seed=seed, n_range=(12, 14))
    ids = [srv.submit(s) for s in reqs]
    srv.drain()
    return srv, reqs, [srv.poll(i) for i in ids]


@pytest.mark.parametrize("n", [1, 3, 4])
def test_flush_spans_form_one_tree_per_flush(warm_root, n):
    from repro.tasks.base import stack_fixed
    obs = Observability(registry=MetricsRegistry())
    srv, reqs, resp = _flush_run(warm_root, obs, n)
    spans = obs.tracer.spans(cat="flush")
    assert sorted(s.name for s in spans) == [
        "flush", "flush.complete", "flush.dispatch", "flush.fetch",
        "flush.stack"]
    by = {s.name: s for s in spans}
    root = by["flush"]
    fid = root.args["flush"]
    assert root.parent is None and root.tid == fid
    assert root.args["bucket"] == 16 and root.args["n_rows"] == 4
    assert root.args["n_live"] == n
    for name in ("flush.stack", "flush.dispatch", "flush.fetch"):
        s = by[name]
        assert s.parent == root.sid and s.tid == fid, name
        assert root.t0 <= s.t0 <= s.t1 <= root.t1, name
    assert by["flush.stack"].t1 <= by["flush.dispatch"].t0
    assert by["flush.dispatch"].t1 <= by["flush.fetch"].t0
    done = by["flush.complete"]
    assert done.parent == root.sid and done.tid == fid
    assert done.args == {"flush": fid} and done.t0 >= root.t1
    for r in resp:
        (solve,) = [s for s in obs.tracer.spans(tid=r.request_id)
                    if s.name == "solve"]
        assert solve.args["flush"] == fid
    # input_bytes: the stacked A, b, x and action arrays, pad rows in.
    A, b, x, acts, _ = stack_fixed(
        [srv.task.prepare(s) for s in reqs],
        [srv.action_space.actions[r.action] for r in resp], 4)
    assert root.args["input_bytes"] == \
        A.nbytes + b.nbytes + x.nbytes + acts.nbytes


def test_annotate_hook_sees_the_ring_inline_spans_in_order(warm_root):
    import contextlib
    calls = []

    def hook(name, **args):
        calls.append((name, args))
        return contextlib.nullcontext()

    obs = Observability(registry=MetricsRegistry(),
                        tracer=Tracer(annotate=hook))
    srv, _, _ = _flush_run(warm_root, obs, 3)
    assert srv.obs.tracer.annotate is hook     # the server kept it
    inline = sorted((s for s in obs.tracer.spans() if s.sid),
                    key=lambda s: s.sid)
    assert [c[0] for c in calls] == [s.name for s in inline]
    assert "flush" in [c[0] for c in calls]
    (root,) = [s for s in inline if s.name == "flush"]
    (args,) = [a for name, a in calls if name == "flush"]
    assert args["flush"] == root.args["flush"] and args["bucket"] == 16
    assert obs.registry.errors == 0


class _Boom:
    def __init__(self, where):
        self.where = where

    def __call__(self, name, **args):
        if self.where == "call":
            raise RuntimeError("hook down")
        return self

    def __enter__(self):
        if self.where == "enter":
            raise RuntimeError("hook down")

    def __exit__(self, *exc):
        if self.where == "exit":
            raise RuntimeError("hook down")


@pytest.mark.parametrize("where", ["call", "enter", "exit"])
def test_raising_annotate_hook_never_changes_responses(warm_root, where):
    _, _, base = _flush_run(warm_root, False, 3)
    obs = Observability(registry=MetricsRegistry(),
                        tracer=Tracer(annotate=_Boom(where)))
    _, _, got = _flush_run(warm_root, obs, 3)
    for r, b in zip(got, base):
        assert r.request_id == b.request_id and r.action == b.action
        assert r.reward == b.reward and r.state == b.state
        assert int(r.record.status) == int(b.record.status)
        assert float(r.record.cost) == float(b.record.cost)
    assert obs.registry.errors >= 5            # one per flush-path span
    text = render_prometheus(obs.registry)
    assert f"repro_obs_errors_total {obs.registry.errors}" in text
    names = {s.name for s in obs.tracer.spans(cat="flush")}
    assert "flush" in names and "flush.complete" in names


def test_reward_gauges_are_set_once_per_flush(warm_root):
    reg = MetricsRegistry()
    writes = []
    reg.add_sink(lambda name, labels, value: writes.append(name))
    srv, _, resp = _flush_run(warm_root, Observability(registry=reg), 3)
    assert len(resp) == 3
    assert writes.count("repro_service_reward_ewma") == 1
    assert writes.count("repro_service_abs_rpe_ewma") == 1
    assert writes.count("repro_service_policy_info") == 1
    text = render_prometheus(reg)
    assert lint_exposition(text) == []
    tel = srv.telemetry
    assert reg.gauge("repro_service_reward_ewma", "", ("task",)).labels(
        task="gmres_ir").value == pytest.approx(tel.reward_ewma.value)
    assert (f'repro_service_policy_info{{task="gmres_ir",'
            f'version="{srv.policy_version}"}} 1') in text


@pytest.mark.parametrize("mode, tau", [("sync", 4.25e-6),
                                       ("background", 4.75e-6)])
def test_warmup_records_lower_and_compile_per_bucket(mode, tau):
    from repro.core import aot
    from repro.core.features import PAPER_FEATURES
    from repro.core import Discretizer, PrecisionPolicy, QTable
    nf = len(PAPER_FEATURES)
    disc = Discretizer.fit(
        np.random.default_rng(0).normal(size=(8, nf)), [2] * nf)
    policy = PrecisionPolicy(SPACE, disc,
                             QTable(disc.n_states, SPACE.n_actions))
    before = aot.cache_stats()
    obs = Observability(registry=MetricsRegistry())
    # A tau no other test uses: the grid's cells are cold here. The
    # background sweep records from its own thread, into the tracer
    # that was current where the server built it.
    srv = AutotuneServer(
        policy, IRConfig(tau=tau, i_max=4, m_max=12),
        batcher_cfg=BatcherConfig(max_batch=2, max_wait_s=0.001,
                                  bucket_step=16, min_bucket=16),
        obs=obs, seed=0, warmup=mode, warmup_buckets=[12, 28])
    if mode == "background":
        srv.warmup.wait(timeout=600)
    assert srv.warmup_state()["done"]
    after = aot.cache_stats()
    spans = obs.tracer.spans(cat="aot")
    for bucket in (16, 32):
        mine = [s for s in spans if s.args["bucket"] == bucket]
        (parent,) = [s for s in mine if s.name == "aot.bucket"]
        for name in ("aot.lower", "aot.compile"):
            (s,) = [s for s in mine if s.name == name]
            assert s.parent == parent.sid
            assert s.args["rows"] == 2 and s.args["executor"] == "local"
            assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
    for key in ("lower_s", "compile_s"):
        name = "aot." + key[:-2]
        spent = sum(s.duration for s in spans if s.name == name)
        assert after[key] - before[key] == pytest.approx(spent, abs=1e-3)
    assert srv.warmup_state()["elapsed_s"] >= \
        round(after["lower_s"] - before["lower_s"], 3)
