"""The dry-run machinery itself, exercised on a small forced-device-count
mesh in a subprocess (the production 512-device sweep runs via
``python -m repro.launch.dryrun --all``; results in EXPERIMENTS.md)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.launch import dryrun

mesh = jax.make_mesh({mesh_shape}, {axes},
                     axis_types=(jax.sharding.AxisType.Auto,) * len({axes}))
rec = dryrun.lower_one("{arch}", "{shape}", mesh=mesh, rules={rules})
print("RESULT " + json.dumps({{
    "dominant": rec["dominant"],
    "flops": rec["flops_per_device"],
    "coll": rec["collective_bytes_per_device"],
    "chips": rec["chips"],
}}))
"""


def _run(arch, shape, mesh_shape=(2, 4), axes=("data", "model"), rules=None):
    code = SCRIPT.format(arch=arch, shape=shape, mesh_shape=mesh_shape,
                         axes=axes, rules=rules)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_lower_train_step_small_mesh():
    rec = _run("whisper-base", "train_4k")
    assert rec["chips"] == 8
    assert rec["flops"] > 0
    assert rec["coll"] > 0          # FSDP all-gathers + grad reduce must show


@pytest.mark.slow
def test_lower_decode_step_small_mesh():
    rec = _run("deepseek-v2-lite-16b", "decode_32k")
    assert rec["flops"] > 0


@pytest.mark.slow
def test_lower_multipod_axes_small_mesh():
    rec = _run("internvl2-1b", "train_4k", mesh_shape=(2, 2, 2),
               axes=("pod", "data", "model"),
               rules={"batch": ("pod", "data"), "fsdp": ("data",),
                      "tp": ("model",)})
    assert rec["chips"] == 8
