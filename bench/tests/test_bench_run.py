"""run.py refuses to run off the chip: non-zero exit and no result line."""

import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "if_static_p80",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own files
    has no program to run: the run fails, with no result line."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "No module named 'repro'" in p.stderr
