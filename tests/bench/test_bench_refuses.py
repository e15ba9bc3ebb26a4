"""The benchmark refuses to report where it cannot measure: with no TPU, and
in a directory that holds only BENCHMARK.json and the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "codeqwen15-7b-L2.batch-decode", "--seed",
        str(2**31 + 3), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout), p.stdout
    assert "TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for rel in paths:
        shutil.copytree(os.path.join(ROOT, rel), tmp_path / rel,
                        ignore=shutil.ignore_patterns(".jax_cache",
                                                      "__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout), p.stdout
