"""Distribution tests: sharding rules + a subprocess mini dry-run on a fake
8-device mesh (the 512-device production dry-run runs via launch/dryrun.py;
artifact validity is asserted here when present)."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def test_param_specs_rules():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_smoke
    from repro.distributed.sharding import param_specs, spec_for_param
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()  # 1 device: every axis size 1 -> all None
    # Use a synthetic 4x4 mesh instead for rule logic:
    from jax.sharding import Mesh
    devs = np.array(jax.devices() * 16)[:16].reshape(4, 4)
    mesh = Mesh(devs, ("data", "model"))

    s = spec_for_param("embed/embedding", (256, 64), mesh)
    assert s == P("model", ("data",))
    s = spec_for_param("layers/l0/mixer/wq", (2, 64, 128), mesh)
    assert s == P(None, ("data",), "model")
    s = spec_for_param("layers/l0/ffn/wi_gate", (2, 8, 64, 128), mesh)
    assert s == P(None, "model", ("data",), None)   # MoE expert bank
    s = spec_for_param("prefix/[0]/ffn/wi_gate", (64, 128), mesh)
    assert s == P(("data",), "model")               # dense FFN
    s = spec_for_param("layers/l0/ln1", (64,), mesh)
    assert s == P()
    # Divisibility: a dim not divisible by the axis drops the axis.
    s = spec_for_param("layers/l0/mixer/wq", (2, 63, 130), mesh)
    assert s == P(None, None, None)
    # Quantized moment leaves inherit the parent param's rule.
    s = spec_for_param("opt/m/layers/l0/mixer/wq/codes", (2, 64, 128), mesh)
    assert s == P(None, ("data",), "model")


def test_cache_specs_rules():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.sharding import cache_spec
    devs = np.array(jax.devices() * 16)[:16].reshape(4, 4)
    mesh = Mesh(devs, ("data", "model"))
    # batch shardable -> batch over data, heads over model
    assert cache_spec("layers/l0/k", (8, 1024, 4, 64), mesh) == \
        P(("data",), None, "model", None)
    # batch=1 long context -> sequence over data
    assert cache_spec("layers/l0/k", (1, 4096, 4, 64), mesh) == \
        P(None, ("data",), "model", None)
    # MLA latent cache
    assert cache_spec("layers/l0/ckv", (8, 1024, 32), mesh) == \
        P(("data",), None, None)
    # mamba state
    assert cache_spec("layers/l0/h", (8, 128, 4), mesh) == \
        P(("data",), "model", None)


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
import numpy as np
from repro.configs import get_smoke
from repro.distributed.sharding import (batch_specs, named, param_specs,
                                        residual_spec)
from repro.launch.specs import train_batch_specs
from repro.models import init_params
from repro.train import AdamWConfig, TrainStepConfig, make_train_step
from repro.train.optimizer import adamw_init
from repro.train.train_step import TrainState
from repro.configs.base import ShapeConfig

cfg = get_smoke("jamba-v0.1-52b")   # exercises mamba+attn+MoE together
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     devices=jax.devices(),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
tcfg = TrainStepConfig(opt=AdamWConfig(quantize_moments=True,
                                       quant_block=16),
                       compute_dtype=jnp.float32)
step = make_train_step(cfg, tcfg,
                       residual_sharding=NamedSharding(mesh,
                                                       residual_spec(mesh)))
key = jax.random.PRNGKey(0)
state_shapes = jax.eval_shape(
    lambda k: TrainState(init_params(cfg, k, jnp.float32),
                         adamw_init(jax.eval_shape(
                             lambda kk: init_params(cfg, kk, jnp.float32),
                             k), tcfg.opt),
                         jnp.zeros((), jnp.int32)), key)
shape = ShapeConfig("mini", 64, 8, "train")
batch_shapes = train_batch_specs(cfg, shape)
state_sh = named(param_specs(state_shapes, mesh), mesh)
batch_sh = named(batch_specs(batch_shapes, mesh), mesh)
with mesh:
    lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                      out_shardings=(state_sh, None)).lower(state_shapes,
                                                            batch_shapes)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # newer JAX: per-module dicts
        cost = cost[0]
    assert float(cost.get("flops", 0)) > 0
    text = compiled.as_text()
assert ("all-reduce" in text) or ("all-gather" in text), "no collectives?!"
print("MINI_DRYRUN_OK")
"""


def test_mini_dryrun_8_devices():
    """Full sharded train-step lower+compile on a fake 2x2x2 pod mesh."""
    env = dict(os.environ,
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    out = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "MINI_DRYRUN_OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.skipif(not glob.glob(os.path.join(ART, "*.json")),
                    reason="production dry-run artifacts not generated yet")
def test_production_dryrun_artifacts_valid():
    """Every artifact the 512-device sweep produced is well-formed."""
    for p in glob.glob(os.path.join(ART, "*.json")):
        with open(p) as f:
            art = json.load(f)
        assert art["n_devices"] in (256, 512), p
        assert art.get("compile_s", 0) > 0, p
        if "flops" in art:
            assert art["flops"] > 0, p
            assert art["model_flops"] > 0, p
