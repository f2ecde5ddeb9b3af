"""Readers of the flush-path and warmup metrics, on synthetic records:
the program's flush spans, its compile timers, and the idle split of
trace_phases.py."""
import pytest

import bench
import trace_phases

T0, T1 = 100.0, 110.0


def record():
    # Two flushes in the window and one before it, which every reader
    # must leave out.
    spans = []
    for fid, t0, stack, dispatch, done, nbytes in (
            (7, 101.0, 0.002, 0.004, 0.010, 6_000_000),
            (8, 103.0, 0.004, 0.008, 0.030, 9_000_000),
            (6, 98.0, 0.5, 0.5, 0.5, 1)):
        spans += [
            ("flush", t0, t0 + 0.1, fid,
             {"flush": fid, "bucket": 256, "input_bytes": nbytes}),
            ("flush.stack", t0, t0 + stack, fid, {}),
            ("flush.dispatch", t0 + stack, t0 + stack + dispatch, fid, {}),
            ("flush.complete", t0 + 0.1, t0 + 0.1 + done, fid,
             {"flush": fid}),
            ("solve", t0, t0 + 0.1, 1, {"flush": fid})]
    return {"t_start": T0, "t_end": T1, "spans": spans,
            "cache": {"dir": "/x", "hits": 12, "misses": 0,
                      "lower_s": 80.5, "compile_s": 9.25},
            "trace": {"busy_s": 1.5, "window_s": 2.0, "kernels": {}}}


def read(name, rec):
    return bench.module("metrics", name).read(rec)


@pytest.mark.parametrize("name, want", [
    ("batcher.stack_s_mean", 0.003),
    ("engine.dispatch_s_mean", 0.006),
    ("service.complete_s_mean", 0.020),
    ("engine.input_mb_per_flush", 7.5),
    ("aot.lower_s", 80.5),
    ("aot.compile_s", 9.25),
])
def test_program_metric(name, want):
    assert read(name, record()) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "batcher.stack_s_mean", "engine.dispatch_s_mean",
    "service.complete_s_mean", "engine.input_mb_per_flush",
    "aot.lower_s", "aot.compile_s"])
def test_program_metric_is_left_out_without_the_program_spans(name):
    # a program without the flush spans and compile timers
    rec = record()
    rec["spans"] = [s for s in rec["spans"] if s[0] == "solve"]
    rec["cache"] = {"dir": "/x", "hits": 12, "misses": 0}
    assert read(name, rec) is None


PHASES = {"window_s": 2.0, "flushes": 3, "idle_transfer_s": 0.125,
          "idle_by_phase": {"flush": 0.05, "flush.fetch": 0.2,
                            "flush.dispatch": 0.1, "flush.stack": 0.01,
                            "flush.complete": 0.04,
                            "between_flushes": 0.1}}


@pytest.mark.parametrize("name, want", [
    ("device.idle_share.in_flush", 100 * 0.36 / 2),
    ("device.idle_share.between_flushes", 100 * 0.14 / 2),
    ("device.idle_share.transfer", 100 * 0.125 / 2),
])
def test_device_metric(name, want, monkeypatch):
    monkeypatch.setattr(trace_phases, "read", lambda rec: PHASES)
    assert read(name, record()) == pytest.approx(want)


def test_idle_split_adds_up_to_the_closed_idle_share(monkeypatch):
    phases = dict(PHASES, window_s=2.0)
    monkeypatch.setattr(trace_phases, "read", lambda rec: phases)
    rec = record()
    rec["trace"]["busy_s"] = 2.0 - sum(phases["idle_by_phase"].values())
    split = read("device.idle_share.in_flush", rec) + read(
        "device.idle_share.between_flushes", rec)
    assert split == pytest.approx(read("device.idle_share.closed", rec))


@pytest.mark.parametrize("name, phases", [
    ("device.idle_share.in_flush", None),
    ("device.idle_share.between_flushes", None),
    ("device.idle_share.transfer", None),
    # a trace without the program's flush annotations
    ("device.idle_share.in_flush", dict(PHASES, flushes=0)),
    ("device.idle_share.between_flushes", dict(PHASES, flushes=0)),
])
def test_device_metric_is_left_out_when_nothing_is_read(name, phases,
                                                        monkeypatch):
    monkeypatch.setattr(trace_phases, "read", lambda rec: phases)
    assert read(name, record()) is None
