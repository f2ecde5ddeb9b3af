"""Train/serve substrate tests: optimizer, quantization, pipeline,
checkpoint, grad sync, serve loop, integration (loss decreases)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_smoke
from repro.data.tokens import TokenPipeline
from repro.train import (AdamWConfig, LMPrecisionPolicy, QTensor,
                         TrainPrecisionController, TrainState,
                         TrainStepConfig, adamw_init, adamw_update,
                         cosine_with_warmup, dequantize_int8,
                         init_train_state, make_train_step, quantize_int8,
                         sync_leaf)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Quantization
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bounded():
    x = jax.random.normal(KEY, (1000,)) * 3.0
    q = quantize_int8(x, block=256)
    err = jnp.abs(dequantize_int8(q, block=256) - x)
    # absmax int8: error <= scale/127 per block
    assert float(jnp.max(err)) <= float(jnp.max(jnp.abs(x))) / 127 + 1e-7
    assert q.codes.dtype == jnp.int8


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 500), st.floats(1e-6, 1e6))
def test_prop_int8_roundtrip(n, scale):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)
    q = quantize_int8(x, block=64)
    back = dequantize_int8(q, block=64)
    assert back.shape == x.shape
    assert float(jnp.max(jnp.abs(back - x))) <= scale * 0.2 + 1e-6


def test_int8_zero_block():
    x = jnp.zeros((300,))
    back = dequantize_int8(quantize_int8(x), 256)
    np.testing.assert_array_equal(np.asarray(back), 0)


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def _quad_params():
    return {"w": jnp.asarray([3.0, -2.0, 1.0]), "b": jnp.asarray([0.5])}


@pytest.mark.parametrize("quant", [False, True])
def test_adamw_minimizes_quadratic(quant):
    cfg = AdamWConfig(weight_decay=0.0, quantize_moments=quant,
                      quant_block=4)
    params = _quad_params()
    state = adamw_init(params, cfg)
    for _ in range(300):
        grads = jax.tree_util.tree_map(lambda p: 2 * p, params)  # d/dp p^2
        params, state, _ = adamw_update(params, grads, state, 0.05, cfg)
    total = sum(float(jnp.sum(jnp.abs(p))) for p in
                jax.tree_util.tree_leaves(params))
    assert total < 0.05


def test_adamw_grad_clip():
    cfg = AdamWConfig(grad_clip=1.0, weight_decay=0.0)
    params = _quad_params()
    state = adamw_init(params, cfg)
    big = jax.tree_util.tree_map(lambda p: p * 1e6, params)
    p2, _, stats = adamw_update(params, big, state, 0.01, cfg)
    assert float(stats["grad_norm"]) > 1e5
    delta = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                zip(jax.tree_util.tree_leaves(p2),
                    jax.tree_util.tree_leaves(params)))
    assert delta < 0.1  # clipped step stays small


def test_quantized_moments_are_int8():
    cfg = AdamWConfig(quantize_moments=True, quant_block=4)
    state = adamw_init(_quad_params(), cfg)
    leaves = jax.tree_util.tree_leaves(
        state.m, is_leaf=lambda x: isinstance(x, QTensor))
    assert all(isinstance(q, QTensor) for q in leaves)


def test_cosine_schedule():
    lr0 = float(cosine_with_warmup(0, peak_lr=1.0, warmup=10, total=100))
    lr_peak = float(cosine_with_warmup(10, peak_lr=1.0, warmup=10,
                                       total=100))
    lr_end = float(cosine_with_warmup(100, peak_lr=1.0, warmup=10,
                                      total=100))
    assert lr0 == 0.0 and lr_peak == pytest.approx(1.0)
    assert lr_end == pytest.approx(0.1, abs=0.01)


# ---------------------------------------------------------------------------
# Token pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_resumable():
    p1 = TokenPipeline(1000, 64, 4, seed=7)
    batches = [p1.next_batch() for _ in range(3)]
    p2 = TokenPipeline(1000, 64, 4, seed=7)
    p2.load_state_dict({"cursor": 2, "seed": 7, "shard": 0, "n_shards": 1})
    np.testing.assert_array_equal(p2.next_batch()["tokens"],
                                  batches[2]["tokens"])


def test_pipeline_shards_disjoint():
    a = TokenPipeline(1000, 32, 2, seed=1, shard=0, n_shards=2).next_batch()
    b = TokenPipeline(1000, 32, 2, seed=1, shard=1, n_shards=2).next_batch()
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_pipeline_learnable_structure():
    p = TokenPipeline(1000, 64, 8, seed=0)
    t = p.next_batch()["tokens"]
    pos = np.arange(64) % 8 == 0
    pred = (np.roll(t, 1, axis=1)[:, pos] * 7 + 3) % 998 + 2
    np.testing.assert_array_equal(t[:, pos][:, 1:], pred[:, 1:])


# ---------------------------------------------------------------------------
# Checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    cfg = get_smoke("granite-3-2b")
    tcfg = TrainStepConfig(opt=AdamWConfig(quantize_moments=True,
                                           quant_block=64))
    state = init_train_state(cfg, KEY, tcfg)
    path = save_checkpoint(str(tmp_path), 5, state,
                           {"pipeline": {"cursor": 3}})
    assert latest_step(str(tmp_path)) == 5
    restored, meta = restore_checkpoint(str(tmp_path), state)
    assert meta["step"] == 5 and meta["pipeline"]["cursor"] == 3
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_pointer_survives_multiple_saves(tmp_path):
    state = {"x": jnp.ones((3,))}
    save_checkpoint(str(tmp_path), 1, state)
    save_checkpoint(str(tmp_path), 2, {"x": jnp.ones((3,)) * 2})
    restored, meta = restore_checkpoint(str(tmp_path), state)
    assert meta["step"] == 2
    assert float(restored["x"][0]) == 2.0


# ---------------------------------------------------------------------------
# Grad sync (cross-pod compression)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,tol", [("fp32", 1e-7), ("bf16", 0.02),
                                      ("int8", 0.05)])
def test_sync_leaf_modes(mode, tol):
    devs = jax.local_devices()
    n = min(len(devs), 1) or 1
    # Single-device: emulate a 1-pod mean via shard_map over a size-1 axis.
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    g = jax.random.normal(KEY, (64,))
    f = jax.shard_map(lambda x: sync_leaf(x, mode), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    out = f(g)
    assert float(jnp.max(jnp.abs(out - g))) <= tol * float(
        jnp.max(jnp.abs(g))) + 1e-6


# ---------------------------------------------------------------------------
# Integration: a tiny model trains; controller reacts to divergence
# ---------------------------------------------------------------------------

def test_train_loss_decreases_smoke():
    cfg = get_smoke("granite-3-2b")
    tcfg = TrainStepConfig(peak_lr=3e-3, warmup=5, total_steps=60)
    state = init_train_state(cfg, KEY, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    pipe = TokenPipeline(cfg.vocab_size, 64, 8, seed=0)
    losses = []
    for _ in range(40):
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5


def test_precision_controller_learns_to_avoid_divergence():
    ctrl = TrainPrecisionController(total_decisions=200, interval=1,
                                    seed=0)
    rng = np.random.default_rng(0)
    # Synthetic world: e4m3 matmuls diverge, bf16/fp32 fine.
    for _ in range(200):
        feats = ctrl.features(1.0, 1e-3)
        pol = ctrl.act(feats)
        lowest = int(ctrl.space.ladder_idx[ctrl._pending[1]][0])
        if lowest == 0:  # e4m3 compute
            ctrl.observe(2.0, 2.5 + rng.random(), diverged=rng.random() < .5)
        else:
            ctrl.observe(2.0, 1.98)
    feats = ctrl.features(1.0, 1e-3)
    pol = ctrl.act(feats)
    a = ctrl._pending[1]
    assert int(ctrl.space.ladder_idx[a][0]) != 0  # avoids e4m3 compute


def test_lm_policy_emulated_matmul_precision():
    from repro.precision import FORMAT_ID
    pol = LMPrecisionPolicy(jnp.asarray(FORMAT_ID["e4m3"], jnp.int32))
    x = jax.random.normal(KEY, (16, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 8), jnp.float32)
    lo = pol.matmul(x, w, "ffn")
    hi = jnp.dot(x, w)
    rel = float(jnp.max(jnp.abs(lo - hi)) / jnp.max(jnp.abs(hi)))
    assert 1e-3 < rel < 0.5
