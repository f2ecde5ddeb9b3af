"""The traffic generators' seeding: every run serves the same pool of
systems (the design's (n, kappa) pairs, matrices from the design seed);
the run seed orders the requests."""
import numpy as np
import pytest

import bench


def small(name):
    cfg = bench.load_json(f"{bench.HERE}/configs/{name}.json")
    cfg["generator"]["n"] = [20, 40]
    return cfg


def test_every_run_serves_the_same_pool():
    cfg = small("dense_gmres_ir")
    a, b = bench.make_pool(cfg, 6), bench.make_pool(cfg, 6)
    for x, y in zip(a, b):
        assert np.array_equal(x["A"], y["A"])
        assert np.array_equal(x["b"], y["b"])
    assert [(s["n"], s["kappa"]) for s in a] == bench.design(cfg, 6)


def test_the_run_seed_orders_the_requests():
    order = bench.module("entries", "inproc").request_order
    a = [next(o) for o in [order(8, 2 ** 31 + 17)] for _ in range(24)]
    b = [next(o) for o in [order(8, 2 ** 31 + 17)] for _ in range(24)]
    c = [next(o) for o in [order(8, 2 ** 31 + 18)] for _ in range(24)]
    assert a == b and a != c
    # each pass over the pool sends every system once
    assert sorted(a[:8]) == sorted(c[8:16]) == list(range(8))


def test_the_open_loop_has_the_same_gaps_for_every_seed():
    client = bench.module("entries", "frontdoor_client")
    cfg = small("dense_gmres_ir")
    tr = {"rate": 4.0, "pool": 8}
    a = client.schedule(cfg, tr, 30.0, 1)
    b = client.schedule(cfg, tr, 30.0, 2)
    assert a == client.schedule(cfg, tr, 30.0, 1) and a != b
    assert 60 < len(a) < 180 and all(t < 30.0 for t, _ in a)


def test_design_is_stratified_over_the_ranges():
    cfg = bench.load_json(f"{bench.HERE}/configs/dense_gmres_ir.json")
    pts = bench.design(cfg, 128)
    ns = sorted(n for n, _ in pts)
    ks = sorted(np.log10(k) for _, k in pts)
    lo, hi = cfg["generator"]["n"]
    klo, khi = cfg["generator"]["log10_kappa"]
    assert lo <= ns[0] and ns[-1] <= hi
    # one point in each of 128 equal strata of each range (n rounded
    # down to a whole size)
    w = (hi - lo + 1) / 128
    assert all(lo + j * w - 1 <= n < lo + (j + 1) * w
               for j, n in enumerate(ns))
    assert all(int((k - klo) / (khi - klo) * 128) == j
               for j, k in enumerate(ks))


def test_randsvd_has_the_stated_condition_number():
    gen = bench.module("generators", "randsvd")
    A, b, x = gen.make(30, 1e4, np.random.default_rng(0),
                       {"sigma_max": 1.0})
    assert np.linalg.cond(A) == pytest.approx(1e4, rel=1e-6)
    assert np.allclose(A @ x, b)

