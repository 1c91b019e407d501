"""``bench/run.py`` finds no TPU here: it exits non-zero and prints no result."""

import os
import shutil
import subprocess
import sys

from spec import BENCH, ROOT

ARGS = ["--workload", "phi4-mini-3.8b.decode-offline", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_make_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
