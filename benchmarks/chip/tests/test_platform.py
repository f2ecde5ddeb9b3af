"""The command refuses a host without a TPU, a device kind peaks.json
does not list, and fewer chips than the cell asks for."""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import run

PEAKS = {"TPU v5 lite": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}


def dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_refuses_a_cpu():
    with pytest.raises(run.NoChip, match="needs a TPU"):
        run.chip(1, [dev("cpu", "cpu")], PEAKS)


def test_refuses_an_unknown_device_kind():
    with pytest.raises(run.NoChip, match="not in peaks.json"):
        run.chip(1, [dev(kind="TPU v9 huge")], PEAKS)


def test_refuses_too_few_chips():
    with pytest.raises(run.NoChip, match="needs 4 chips"):
        run.chip(4, [dev()], PEAKS)


def test_accepts_a_listed_chip():
    devices, peak = run.chip(1, [dev()], PEAKS)
    assert peak == PEAKS["TPU v5 lite"]


def test_command_exits_nonzero_without_printing_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "dense_gmres.inproc.closed32", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=run.REPO, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout
