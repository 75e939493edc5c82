"""`chip_smoke.py`: refuses to run without an accelerator, and every phase
runs on the CPU when handed the reduced config (the CPU rehearsal of the
chip run; the script itself has no size option)."""
import json
import os
import subprocess
import sys

import pytest

import jax

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

REDUCED = chip_smoke.SmokeConfig().reduced()


def test_refuses_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "cpu" in out.stderr
    assert '"ok"' not in out.stdout


def test_grid_phase_matches_legacy_loop():
    rec = chip_smoke.phase_grid(REDUCED)
    assert rec["legacy_cells"] == 3
    assert all(v <= 1.0 for v in
               rec["legacy_worst_share_of_tolerance"].values())


def test_serve_phase_compiles_three_programs():
    jax.clear_caches()          # count from a cold jit cache
    rec = chip_smoke.phase_serve(REDUCED)
    assert rec["engine_programs"] == 3


@pytest.mark.zoo
def test_zoo_phase_resume_is_bit_equal():
    rec = chip_smoke.phase_zoo(REDUCED)
    assert rec["resume_bit_equal"]
    first, last = rec["loss_first_last"]
    assert last < first


_MESH = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
print("RESULT " + json.dumps(
    chip_smoke.phase_zoo_mesh(chip_smoke.SmokeConfig().reduced())))
"""


@pytest.mark.zoo
def test_zoo_mesh_phase_on_four_virtual_devices():
    """One model per device over a 4-device mesh against each scenario
    alone (a child interpreter, so the forced device count stays out of
    this process)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH, ROOT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    rec = json.loads(line[-1][len("RESULT "):])
    assert rec["chips"] == 4
    assert rec["max_relative_loss_difference"] <= 1e-5
