"""Readers of the double-word carrier's metrics, on a synthetic record:
the `dw_gmres`/`dw_lu` arguments of the program's flush spans and the
actions the answers ran."""
import pytest

import bench

T0, T1 = 100.0, 110.0


def read(name, rec):
    return bench.module("metrics", name).read(rec)


def dw_record():
    # Three flushes in the window (one took DW GMRES, one the DW LU and
    # DW GMRES, one neither) and one before it, which every reader must
    # leave out; two requests in the 0.2 s flush, one in the others.
    spans = []
    for fid, t0, solve_s, lu, gmres in ((1, 98.0, 9.0, True, True),
                                        (2, 101.0, 0.2, False, True),
                                        (3, 102.0, 0.6, True, True),
                                        (4, 103.0, 0.05, False, False)):
        spans.append(("flush", t0, t0 + solve_s, fid,
                      {"flush": fid, "bucket": 256, "dw_lu": lu,
                       "dw_gmres": gmres}))
        for rid in range(2 if fid == 2 else 1):
            spans.append(("solve", t0, t0 + solve_s, 10 * fid + rid,
                          {"flush": fid, "bucket": 256}))
    answers = [{"action_names": names} for names in (
        ["fp32", "fp32", "fp32", "fp64"], ["fp64"] * 4,
        ["bf16", "fp32", "fp32", "fp32"], ["fp32", "fp32", "fp64", "fp64"])]
    return {"t_start": T0, "t_end": T1, "spans": spans, "answers": answers}


@pytest.mark.parametrize("name, want", [
    ("solver.dw_gmres_flush_share", 100 * 2 / 3),
    ("solver.dw_solve_s_mean", (0.2 + 0.6) / 2),
    ("solver.fp64_step_row_share", 75.0),
])
def test_double_word_metric(name, want):
    assert read(name, dw_record()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["solver.dw_gmres_flush_share",
                                  "solver.dw_solve_s_mean"])
def test_double_word_metric_is_left_out_on_the_f32_carrier(name):
    # a program whose flush spans carry no double-word args
    rec = dw_record()
    rec["spans"] = [(n, t0, t1, tid, {k: v for k, v in kw.items()
                                      if not k.startswith("dw_")})
                    for n, t0, t1, tid, kw in rec["spans"]]
    assert read(name, rec) is None
