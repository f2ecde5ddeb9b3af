"""The main path's Pallas kernels compile for a TPU v5e.

Compiles `chop`, `qmv`, `qgemm` and `trisolve` with ``interpret=False``
for a described (not attached) ``v5e:2x2`` topology, at the serving
bucket widths, each under ``jax.vmap`` with per-row format ids as the
solvers call them, with x64 on as the service runs. The TPU compiler
refuses here what a chip would refuse — misaligned blocks, 64-bit
integers inside a kernel, ops Mosaic cannot lower — at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports every test file.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chop import chop_op
from repro.kernels.qmatmul import qgemm_op, qmv_op
from repro.kernels.trisolve import trisolve_op

WIDTHS = (128, 256, 512)
BATCH = 8
PANEL = 64          # blocked-LU panel: the qgemm K the solver passes

# The benchmark's roofline reader: it finds a kernel by its op name and
# counts its work from the compiled custom call's operand shapes.
_FLOPS_SPEC = importlib.util.spec_from_file_location(
    "chip_flops", os.path.join(os.path.dirname(__file__), "..",
                               "benchmarks", "chip", "flops.py"))
flops = importlib.util.module_from_spec(_FLOPS_SPEC)
_FLOPS_SPEC.loader.exec_module(flops)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device, with the persistent compile cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    assert jax.config.jax_enable_x64
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_calls(text: str, name: str) -> int:
    return sum(1 for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and f"/{name}/pallas_call" in line)


@pytest.mark.parametrize("n", WIDTHS)
def test_chop_compiles_for_v5e(one_chip, n):
    def f(x, fid):
        return jax.vmap(lambda xi, fi: chop_op(xi, fi))(x, fid)
    text = _compiled_text(f, _shape(one_chip, (BATCH, n, n), jnp.float32),
                          _shape(one_chip, (BATCH,), jnp.int32))
    assert _kernel_calls(text, "chop") >= 1


@pytest.mark.parametrize("n", WIDTHS)
def test_qmv_compiles_for_v5e(one_chip, n):
    def f(a, v, fid):
        return jax.vmap(lambda ai, vi, fi: qmv_op(ai, vi, fi))(a, v, fid)
    text = _compiled_text(f, _shape(one_chip, (BATCH, n, n), jnp.float32),
                          _shape(one_chip, (BATCH, n), jnp.float32),
                          _shape(one_chip, (BATCH,), jnp.int32))
    assert _kernel_calls(text, "qmv") >= 1


@pytest.mark.parametrize("n", WIDTHS)
def test_qgemm_compiles_for_v5e(one_chip, n):
    """The blocked LU's first trailing update: (n - 64, 64) x (64, n - 64)."""
    m = n - PANEL

    def f(a, b, fid):
        return jax.vmap(lambda ai, bi, fi: qgemm_op(ai, bi, fi))(a, b, fid)
    text = _compiled_text(f, _shape(one_chip, (BATCH, m, PANEL),
                                    jnp.float32),
                          _shape(one_chip, (BATCH, PANEL, m), jnp.float32),
                          _shape(one_chip, (BATCH,), jnp.int32))
    assert _kernel_calls(text, "qmatmul") >= 1


@pytest.mark.parametrize("lower", [True, False], ids=["lower", "upper"])
@pytest.mark.parametrize("n", WIDTHS)
def test_trisolve_compiles_for_v5e(one_chip, n, lower):
    def f(lu, b, fid):
        return jax.vmap(lambda li, bi, fi: trisolve_op(
            li, bi, fi, lower=lower))(lu, b, fid)
    text = _compiled_text(f, _shape(one_chip, (BATCH, n, n), jnp.float32),
                          _shape(one_chip, (BATCH, n), jnp.float32),
                          _shape(one_chip, (BATCH,), jnp.int32))
    assert _kernel_calls(text, "trisolve") == 1
    # One launch for the whole batch, its factor operand the vmapped
    # call's column blocks: the shape `trisolve_roofline` counts.
    line = next(ln for ln in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln
                and "/trisolve/pallas_call" in ln)
    _, kernel, results, operands = flops.parse_custom_call(line)
    assert kernel == "trisolve"
    assert flops._largest(operands) == (BATCH, n // 128, n, 128)
    assert flops.cost(kernel, results, operands)[0] == BATCH * n * n
