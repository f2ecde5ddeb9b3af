"""The reduction from a profiler trace to device metrics: on synthetic
events, and on a small trace recorded on a TPU v5e (one GMRES-IR batch
of 8 systems at bucket 256, all four Pallas kernels in it)."""
import gzip
import os
import shutil

import pytest

import trace_reduce

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "gmres_ir_b256.xplane.pb.gz")

QMV = ('%qmv.3 = f32[8,256,1]{2,1,0} custom-call(s32[8,1,5]{2,1,0} %a, '
       'f32[8,256,256]{2,1,0} %b, f32[8,1,256]{2,1,0} %c), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints='
       '{s32[8,1,5]{2,1,0}, f32[8,256,256]{2,1,0}, f32[8,1,256]{2,1,0}}')


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_self_time_subtracts_nested_ops():
    events = [("%while.1 = (f32[2]) while(...)", 0, 100),
              (QMV, 10, 30), ("%fusion.2 = f32[2] fusion(...)", 40, 50)]
    st = trace_reduce.self_times(events)
    assert st["while"] == pytest.approx(70e-9)
    assert st["qmv"] == pytest.approx(20e-9)
    assert st["fusion"] == pytest.approx(10e-9)


def test_kernel_roofline_inputs():
    k = trace_reduce.kernels([(QMV, 0, 1000), (QMV, 2000, 3000)], PEAK)
    q = k["qmv"]
    assert q["calls"] == 2 and q["seconds"] == pytest.approx(2e-6)
    # 2 calls of 8 x 256 x 256: memory-bound at 819 GB/s
    one = 4 * (8 * 256 + 8 * 5 + 8 * 256 * 256 + 8 * 256)
    assert q["bytes"] == 2 * one and q["bound"] == "memory"
    assert q["bound_s"] == pytest.approx(2 * one / 819e9)


def test_idle_gaps_go_to_the_innermost_host_span():
    busy = [[10, 20], [50, 60]]
    host = [("bench.window", 0, 100), ("bench.step", 0, 40),
            ("solve_rows", 25, 35)]
    gaps = dict(trace_reduce.idle_gaps(busy, host, 0, 100))
    # 0-10 in bench.step, 20-50 mid 35 in solve_rows, 60-100 mid 80 in
    # bench.window
    assert gaps == {"bench.step": pytest.approx(10e-9),
                    "solve_rows": pytest.approx(30e-9),
                    "bench.window": pytest.approx(40e-9)}


def test_reduce_busy_and_window():
    devices = {"/device:TPU:0": [(QMV, 10, 20), ("%copy.1 = f32[2]", 15,
                                                 30)]}
    r = trace_reduce.reduce(devices, [("bench.window", 0, 40)], (0, 40),
                            PEAK)
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["idle_gaps"] == [["bench.window", pytest.approx(20e-9)]]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(RECORDED, "rb") as src, \
            open(d / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace_reduce.reduce_dir(str(d), PEAK)


def test_recorded_trace_has_the_four_kernels(recorded):
    assert set(recorded["kernels"]) == {"chop", "qmv", "qmatmul",
                                        "trisolve"}


def test_recorded_trace_shares_stay_under_the_roofline(recorded):
    assert 0 < recorded["busy_s"] <= recorded["window_s"]
    for name, k in recorded["kernels"].items():
        assert 0 < k["bound_s"] <= k["seconds"], name
        assert k["bound"] == "memory", name
    kernel_s = sum(k["seconds"] for k in recorded["kernels"].values())
    assert kernel_s <= recorded["busy_s"]


def test_recorded_trace_breakdown(recorded):
    ops = dict(recorded["device_ops"])
    # the blocked substitution dominates a GMRES-IR batch at 256
    assert max(ops, key=ops.get) == "trisolve"
    assert recorded["idle_gaps"]
    assert sum(v for _, v in recorded["idle_gaps"]) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6)
