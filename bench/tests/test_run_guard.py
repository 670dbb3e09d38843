"""``bench/run.py`` refuses to measure anywhere but on the chip, and cannot
run from the benchmark's files alone."""
import json
import os
import shutil
import subprocess
import sys

from bench.lib import spec

ARGS = ["--workload", "deepffm.sessions", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = _run(spec.ROOT, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_exits_nonzero_with_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "No module named 'repro'" in p.stderr
