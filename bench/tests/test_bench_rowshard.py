"""The row-sharded solve at a tiny size on four virtual CPU devices, and
the fault of the exchange between chips left out.

``rowshard.fresh`` (``bench/configs/rowshard_m2p22_n1000.json`` through
``sketched_lstsq``) is not yet a cell of ``BENCHMARK.json`` (PERF.md §7);
the test adds it to a copy, as a later PR would add it.  Each run is a
child process: the device count is fixed when JAX starts.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CELL = {"name": "rowshard.fresh", "config": "rowshard_m2p22_n1000",
        "traffic": "fresh_solves", "chips": 4, "why": "x"}
CONFIG = {"name": "rowshard_m2p22_n1000", "source": "x", "reduced": [],
          "file": "bench/configs/rowshard_m2p22_n1000.json", "why": "x"}

CHILD = r"""
import json, sys
from pathlib import Path
sys.path[:0] = [{src!r}, {root!r}]
if {drop_psum}:
    import repro.core.distributed as dist

    class _NoExchange:
        def __getattr__(self, name):
            import jax
            return getattr(jax.lax, name)

        @staticmethod
        def psum(x, axes):
            return x  # each chip keeps its own part: nothing is exchanged

    dist.lax = _NoExchange()
from bench.tests import _tiny
copy = Path({copy!r})
res = _tiny.run("rowshard.fresh", root=copy, bench_dir=copy / "bench")
print("RESULT " + json.dumps(res))
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append(CONFIG)
    b["workloads"].append(CELL)
    for m in b["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append(CELL["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "workloads" / "rowshard.fresh.json").write_text(
        json.dumps({"limits": {"max_rel_err": 1e-2}}))
    return root


def _child(copy, drop_psum: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(src=str(ROOT / "src"), root=str(ROOT),
                        copy=str(copy), drop_psum=drop_psum)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_rowshard_runs_on_four_devices_and_is_correct(copy):
    res = _child(copy, drop_psum=False)
    assert res["correct"] is True
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"solve_s", "setup_s"}


def test_exchange_between_chips_left_out_is_not_correct(copy):
    res = _child(copy, drop_psum=True)
    assert res["correct"] is False
