"""bench/run.py as the driver runs it: no result without a chip, or
outside a checkout."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script), "--workload", "fig3.fresh", "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_refuses_without_an_accelerator():
    out = _run(ROOT, ROOT / "bench" / "run.py")
    assert out.returncode != 0
    assert _result_lines(out.stdout) == []
    assert "no TPU" in out.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, tmp_path / "bench" / "run.py")
    assert out.returncode != 0
    assert _result_lines(out.stdout) == []
