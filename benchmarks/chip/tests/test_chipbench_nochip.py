"""Without a TPU, or without the system beside it, a run prints no result
and exits non-zero."""
import os
import shutil
import subprocess
import sys

import cells

ARGS = ["--workload", "ycsbc-grid", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cwd / ".jax_cache"))
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_no_result(tmp_path):
    p = _run(cells.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
