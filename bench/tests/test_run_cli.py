"""run.py refuses to run where there is no TPU, and where the checkout
holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from bench import run

ARGS = ["--workload", "r19.bfs.serial", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def test_no_tpu_no_result():
    p = _run(run.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p.stdout)


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".store*",
                                                  "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p.stdout)
