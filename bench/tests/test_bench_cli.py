"""``bench/run.py`` as the benchmark's command line starts it: no result
without a TPU, and none in a copy that holds only the benchmark."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "bitnet2b-int8.chat", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_result_without_a_tpu():
    p = run(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_no_result_in_a_copy_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
