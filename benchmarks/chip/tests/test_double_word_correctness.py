"""`correct` for the double-word configuration: sound answers on its
48-bit carrier pass, and its control, the reference on the f32 carrier
(fp64 residuals computed as f32 residuals), does not."""
import copy

import bench
import correctness


def small_config(name):
    cfg = copy.deepcopy(bench.load_json(
        f"{bench.HERE}/configs/{name}.json"))
    cfg["generator"]["n"] = [100, 128]
    cfg["buckets"] = [128]
    cfg["correct"]["sample"] = 8
    return cfg


def ladder_answers(cfg, pool, carrier_t):
    """One answer per system under an arm of the ladder, as the
    reference on the carrier gives them."""
    names = [["bf16", "fp32", "fp32", "fp32"], ["tf32", "fp32", "fp64",
             "fp64"], ["fp64"] * 4, ["bf16", "tf32", "fp32", "fp64"]]
    out = []
    for i, s in enumerate(pool):
        a = names[i % len(names)]
        st, ferr, nbe, _, inner = correctness.reference(cfg, s, a,
                                                        carrier_t)
        out.append({"i": i, "n": s["n"], "action_names": a, "status": st,
                    "ferr": ferr, "nbe": nbe, "inner": inner})
    return out


def test_the_f32_carrier_is_the_double_word_configurations_control():
    cfg = small_config("dense_gmres_ir_dw")
    assert (cfg["carrier_t"], cfg["control_t"]) == (48, 24)
    pool = bench.make_pool(cfg, 6)
    sound = ladder_answers(cfg, pool, carrier_t=48)
    numbers, detail = correctness.compare(cfg, pool, sound, 3)
    assert detail and correctness.verdict(numbers)
    ctrl = correctness.control(cfg, pool, sound)
    numbers, _ = correctness.compare(cfg, pool, ctrl, 3)
    assert numbers["answer_gap"]["value"] > cfg["correct"]["answer_gap"]
    assert not correctness.verdict(numbers)
