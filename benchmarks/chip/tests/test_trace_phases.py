"""The split of idle device time by the program's flush spans and by
the runtime's transfer work: on synthetic events, and on the trace
recorded on a TPU v5e (one GMRES-IR batch of 8 systems at bucket 256),
which holds no program spans."""
import gzip
import os
import shutil

import pytest

import trace_phases
import trace_reduce

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "gmres_ir_b256.xplane.pb.gz")
BUSY = [[10, 20], [50, 60], [80, 90]]   # gaps 0-10, 20-50, 60-80, 90-100


@pytest.mark.parametrize("host, want", [
    ([], {"between_flushes": 70e-9}),
    # one flush over 15-70: gap 20-50 in its fetch, 60-80 (mid 70) in
    # the flush itself; the rest between flushes
    ([("flush#flush=3,bucket=256#", 15, 70), ("flush.stack", 15, 18),
      ("flush.fetch", 25, 60)],
     {"flush.fetch": 30e-9, "flush": 20e-9, "between_flushes": 20e-9}),
    # completion after the flush, and a bench span that is not a flush's
    ([("bench.step", 0, 100), ("flush", 15, 40),
      ("flush.complete", 60, 90)],
     {"flush": 30e-9, "flush.complete": 20e-9,
      "between_flushes": 20e-9}),
])
def test_idle_goes_to_the_innermost_flush_span(host, want):
    got = trace_phases.idle_by_phase(BUSY, host, 0, 100)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(70e-9)


@pytest.mark.parametrize("threads, want", [
    ([], 0.0),
    ([("Transpose", 0, 5)], 5e-9),                 # inside gap 0-10
    ([("XlaLinearize", 5, 30), ("Transpose", 8, 12)], 15e-9),  # 5-10, 20-30
    ([("D2H Dispatch", 12, 18)], 0.0),             # device busy then
    ([("H2D Dispatch", 55, 95),                    # 60-80 and 90-95
      ("tpu::System::TransferFromDevice", 70, 75)], 25e-9),
    ([("DelinearizeUsingTranspose", 0, 100), ("flush", 0, 100)], 0.0),
])
def test_transfer_work_over_idle_time(threads, want):
    assert trace_phases.idle_transfer_s(BUSY, threads, 0, 100) == \
        pytest.approx(want, abs=1e-15)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(RECORDED, "rb") as src, \
            open(d / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return d


def test_recorded_trace_is_all_between_flushes(recorded):
    ph = trace_phases.reduce_file(str(recorded / "t.xplane.pb"))
    red = trace_reduce.reduce_dir(str(recorded), PEAK)
    assert ph["window_s"] == red["window_s"]
    assert ph["flushes"] == 0
    assert list(ph["idle_by_phase"]) == ["between_flushes"]
    assert ph["idle_by_phase"]["between_flushes"] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert ph["idle_by_phase"]["between_flushes"] == pytest.approx(
        7.8075e-3, abs=1e-7)
    # relayout (XlaLinearize, Transpose) before the program, transfers
    # of the six results after it: 4.5 ms of the 7.8
    assert ph["idle_transfer_s"] == pytest.approx(4.5233e-3, abs=1e-7)


def test_read_finds_the_trace_of_the_record(recorded, monkeypatch):
    monkeypatch.setattr(trace_phases, "TRACE_DIR", str(recorded))
    red = trace_reduce.reduce_dir(str(recorded), PEAK)
    ph = trace_phases.read({"trace": red})
    assert ph is not None and ph["window_s"] == red["window_s"]
    assert trace_phases.read({}) is None                # untraced
    other = dict(red, window_s=red["window_s"] * 2)     # another trace's
    assert trace_phases.read({"trace": other}) is None
    monkeypatch.setattr(trace_phases, "TRACE_DIR", str(recorded / "none"))
    assert trace_phases.read({"trace": red}) is None
