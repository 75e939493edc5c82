"""Loop-aware HLO cost model vs analytic ground truth (subprocess so the
forced device count does not leak into other tests)."""
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
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.roofline.analysis import xla_cost_analysis
from repro.roofline.hlo_cost import analyze_hlo_text

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
L, B, D = 7, 8, 128

def f(x, w):
    def body(c, wi):
        return jnp.tanh(c @ wi), None
    y, _ = jax.lax.scan(body, x, w)
    return y.sum()

x = jax.ShapeDtypeStruct((B, D), jnp.float32,
                         sharding=NamedSharding(mesh, P("data", None)))
w = jax.ShapeDtypeStruct((L, D, D), jnp.float32,
                         sharding=NamedSharding(mesh, P(None, None, "model")))
comp = jax.jit(jax.grad(lambda x, w: f(x, w), argnums=1)).lower(x, w
                                                                ).compile()
c = analyze_hlo_text(comp.as_text())
xla = xla_cost_analysis(comp).get("flops", 0.0)
print("RESULT " + json.dumps({
    "flops": c.flops, "xla": xla, "coll": dict(c.collective),
    "bytes": c.bytes,
}))
"""


@pytest.mark.slow
def test_scan_flops_counted_with_trip_count():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    # analytic: per device per iter: fwd dot (B/2, D)x(D, D/4) = 2*4*32*128,
    # bwd two dots of the same size; 7 iterations, 3 dots each
    expected = 7 * 3 * 2 * 4 * 32 * 128
    assert rec["flops"] == pytest.approx(expected, rel=0.05)
    # the uncorrected XLA count misses the trip multiplier
    assert rec["xla"] < rec["flops"] / 3
    # FSDP-style all-gathers inside the loop must be visible
    assert rec["coll"].get("all-gather", 0) > 0
    assert rec["bytes"] > 0


def test_parser_handles_synthetic_module():
    from repro.roofline.hlo_cost import analyze_hlo_text

    hlo = """
HloModule test

%body (p: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %p = (s32[], f32[4,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  %x = f32[4,8]{1,0} get-tuple-element(%p), index=1
  %w = f32[8,8]{1,0} constant({...})
  %d = f32[4,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[4,16]{1,0} all-gather(%d), dimensions={1}
  %s = f32[4,8]{1,0} slice(%ag), slice={[0:4],[0:8]}
  ROOT %t = (s32[], f32[4,8]) tuple(%ni, %s)
}

%cond (p2: (s32[], f32[4,8])) -> pred[] {
  %p2 = (s32[], f32[4,8]) parameter(0)
  %i2 = s32[] get-tuple-element(%p2), index=0
  %lim = s32[] constant(5)
  ROOT %cmp = pred[] compare(%i2, %lim), direction=LT
}

ENTRY %main (a: f32[4,8]) -> f32[4,8] {
  %a = f32[4,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[4,8]) tuple(%zero, %a)
  %wh = (s32[], f32[4,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[4,8]{1,0} get-tuple-element(%wh), index=1
}
"""
    c = analyze_hlo_text(hlo)
    assert c.flops == 5 * 2 * 4 * 8 * 8          # 5 trips × dot flops
    assert c.collective["all-gather"] == 5 * 4 * 16 * 4
