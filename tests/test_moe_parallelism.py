"""psum vs all-to-all expert parallelism must agree (subprocess: 8 forced
host devices, 2×4 mesh, high capacity so no tokens drop)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import ARCHS
from repro.models import model_zoo
from repro.models.common import init_params, mesh_context, DEFAULT_RULES

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
base = ARCHS["qwen2-moe-a2.7b"].reduced()
base = base.with_(moe=dataclasses.replace(
    base.moe, num_experts=8, num_experts_unpadded=8, capacity_factor=16.0,
    aux_loss_weight=0.0))
key = jax.random.PRNGKey(0)
tokens = jax.random.randint(key, (4, 16), 0, base.vocab_size)

outs = {}
for mode in ("psum", "alltoall"):
    cfg = base.with_(moe=dataclasses.replace(base.moe, parallelism=mode))
    params = init_params(model_zoo.param_defs(cfg), key, jnp.float32)
    with mesh_context(mesh, DEFAULT_RULES):
        logits, aux = jax.jit(
            lambda p, t: model_zoo.forward(p, cfg, {"tokens": t},
                                           remat="none"))(params, tokens)
    outs[mode] = (np.asarray(logits), float(aux))

err = float(np.max(np.abs(outs["psum"][0] - outs["alltoall"][0])))
print("RESULT " + json.dumps({"err": err,
                              "aux_psum": outs["psum"][1],
                              "aux_a2a": outs["alltoall"][1]}))
"""


@pytest.mark.slow
def test_a2a_matches_psum_expert_parallelism():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    assert rec["err"] < 1e-4, rec
