"""Each per-layer metric's reader on a synthetic run record."""
import json
import os

import pytest

import bench

T0, T1 = 100.0, 110.0


def record():
    # Two flushes in the window: bucket 256 with 3 live rows of 8
    # (inner iterations 10, 20, 40), bucket 512 with 8 rows of 5 each;
    # one flush before the window that every reader must leave out.
    spans, answers = [], []
    rid = 0
    for (t0, t1, bucket, its) in ((101.0, 101.5, 256, [10, 20, 40]),
                                  (103.0, 104.0, 512, [5] * 8),
                                  (99.0, 99.5, 128, [1])):
        for it in its:
            spans.append(("queue_wait", t0 - 0.1 * (rid + 1), t0, rid, {}))
            spans.append(("solve", t0, t1, rid,
                          {"bucket": bucket, "n_rows": 8}))
            answers.append({"rid": rid, "inner": it, "status": 0})
            rid += 1
    return {
        "t_start": T0, "t_end": T1, "spans": spans, "answers": answers,
        "counters": ({"solver_rows": 8, "padded_rows": 0,
                      "solve_batch_sum": 0.5, "solve_batch_count": 1},
                     {"solver_rows": 24, "padded_rows": 5,
                      "solve_batch_sum": 2.0, "solve_batch_count": 3}),
        "warmup_s": 42.5,
        "trace": {"busy_s": 1.5, "window_s": 2.0, "kernels": {
            "qmv": {"seconds": 0.5, "bound_s": 0.05, "calls": 10},
            "chop": {"seconds": 0.25, "bound_s": 0.0, "calls": 3}}},
    }


def read(name, rec):
    return bench.module("metrics", name).read(rec)


def test_queue_wait_median_of_window_spans():
    # the window's 11 queue waits are 0.1 .. 1.1 s: median 0.6
    assert read("batcher.queue_wait_s_p50", record()) == pytest.approx(0.6)


def test_pad_row_share_is_the_window_delta():
    assert read("batcher.pad_row_share", record()) == pytest.approx(
        100 * 5 / 16)


def test_solve_batch_mean_is_the_histogram_delta():
    assert read("engine.solve_batch_s_mean", record()) == pytest.approx(
        1.5 / 2)


def test_useful_inner_iterations_per_flush():
    # (10 + 20 + 40) / (8 * 40) and (8 * 5) / (8 * 5), pooled
    assert read("solver.useful_inner_iter_share", record()) == \
        pytest.approx(100 * (70 + 40) / (320 + 40))


def test_idle_share_from_busy_and_window():
    assert read("device.idle_share.closed", record()) == pytest.approx(25)


def test_pallas_share_of_busy_time():
    assert read("kernels.pallas_busy_share", record()) == pytest.approx(50)


def test_roofline_of_a_kernel_that_ran():
    assert read("qmv_roofline", record()) == pytest.approx(10)


@pytest.mark.parametrize("name", ["qmatmul_roofline", "trisolve_roofline",
                                  "chop_roofline"])
def test_roofline_is_left_out_when_there_is_nothing_to_read(name):
    # qmatmul and trisolve did not run; chop's bound is nought
    assert read(name, record()) is None


def test_warmup_seconds():
    assert read("aot.warmup_s", record()) == 42.5


def test_device_metrics_are_left_out_of_an_untraced_record():
    rec = record()
    del rec["trace"]
    for name in ("device.idle_share.closed", "kernels.pallas_busy_share",
                 "qmv_roofline"):
        assert read(name, rec) is None


def test_every_listed_metric_has_a_reader():
    bench_json = bench.benchmark()
    for m in bench_json["per_layer"]:
        assert os.path.exists(os.path.join(bench.HERE, "metrics",
                                           m["name"] + ".py")), m["name"]
    assert json.dumps(bench_json)


def test_closed_loop_tail_counts_failures_as_infinite():
    rec = {"answers": [{"t_submit": 0.0, "t_done": float(k + 1),
                        "status": 0, "expired": False} for k in range(19)]
           + [{"t_submit": 0.0, "t_done": 0.5, "status": 3,
               "expired": False}], "unanswered": 0}
    assert read("latency_p95_s.closed", rec) == 19.0
    rec["unanswered"] = 1
    assert read("latency_p95_s.closed", rec) == float("inf")
