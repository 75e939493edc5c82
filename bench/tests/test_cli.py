"""The command refuses to measure anything without a TPU, and refuses to
run in a directory that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

from bench import spec

CMD = [sys.executable, "-m", "bench.run", "--workload", "internvl2-1b.spot",
       "--seed", "2147483658", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run(spec.ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "tokens_per_s" not in out.stdout and "{" not in out.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert "{" not in out.stdout
