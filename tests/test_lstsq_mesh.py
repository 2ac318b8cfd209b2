"""``lstsq`` on a row-sharded A takes the mesh path: four virtual CPU
devices, one child process (the device count is fixed when JAX starts).

The problem is rowshard.fresh's at a small size: 2^14 x 128 f32, cond 1e4,
beta 1e-3 (a residual, so each device's own rows do not give the answer
and a solve that skips the exchange between devices is wrong).  Both
``lstsq`` and ``sketched_lstsq`` are held to the float64 Householder
solution (``core/direct.py``) within TOL = 10 cond eps32, the accuracy a
float32 solve of a problem of condition cond is asked for (fig3.serve's
SLO); the sound solves read ~4e-4, the solve without the psum ~0.3.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")
COND = 1e4
TOL = 10 * COND * float(np.finfo(np.float32).eps)

# Each option the mesh path has no form for, as lstsq keyword arguments.
REFUSED = {
    "accuracy_high": {"accuracy": "high"},
    "accuracy_certified": {"accuracy": "certified"},
    "reg": {"reg": 0.1},
    "precision_mixed": {"precision": "mixed"},
    "cluster": {"cluster": "cluster-spec"},
    "method_iterative": {"method": "iterative"},
    "method_direct": {"method": "direct"},
    "sketch_srht": {"sketch": "srht"},
    "sketch_gaussian": {"sketch": "gaussian"},
}

CHILD = r"""
import json, sys
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.core import generate_problem, lstsq, qr_solve, sketched_lstsq
import repro.core.distributed as dist
from repro.core.distributed import shard_rows
from repro.sharding import make_mesh

refused = json.loads(sys.argv[1])
mesh = make_mesh((4,), ("data",))
prob = generate_problem(jax.random.key(0), 1 << 14, 128, cond={cond},
                        beta=1e-3, dtype=jnp.float32, method="fast")
x64 = np.asarray(qr_solve(prob.A.astype(jnp.float64),
                          prob.b.astype(jnp.float64)))
A, b = shard_rows(mesh, ("data",), prob.A, prob.b)
key = jax.random.key(1)

def gap(x):
    return float(np.linalg.norm(np.asarray(x, np.float64) - x64)
                 / np.linalg.norm(x64))

out = {{"A_devices": len(A.sharding.device_set)}}
res = lstsq(A, prob.b, key)  # b on one device: lstsq places it
out["lstsq"] = {{"gap": gap(res.x), "method": res.method,
                "istop": int(res.istop), "itn": int(res.itn)}}
res = sketched_lstsq(A, b, key, mesh=mesh)
out["sketched_lstsq"] = {{"gap": gap(res.x), "method": res.method}}
out["raised"] = {{}}
for name, kw in refused.items():
    try:
        lstsq(A, b, key, **kw)
        out["raised"][name] = None
    except ValueError as e:
        out["raised"][name] = str(e)

class _NoExchange:
    def __getattr__(self, name):
        return getattr(jax.lax, name)

    @staticmethod
    def psum(x, axes):
        return x  # each device keeps its own part: nothing is exchanged

jax.clear_caches()
dist.lax = _NoExchange()
out["no_exchange"] = {{"gap": gap(lstsq(A, b, key).x)}}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", CHILD.format(cond=COND), json.dumps(REFUSED)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_lstsq_on_a_row_sharded_a_runs_saa_on_the_mesh(child):
    assert child["A_devices"] == 4
    res = child["lstsq"]
    assert res["method"] == "saa"
    assert res["istop"] not in (3, 6, 7)
    assert res["gap"] < TOL


def test_sketched_lstsq_matches_the_householder_solution(child):
    assert child["sketched_lstsq"]["method"] == "saa"
    assert child["sketched_lstsq"]["gap"] < TOL
    # one program: lstsq's mesh path is sketched_lstsq on the same key
    assert child["sketched_lstsq"]["gap"] == pytest.approx(
        child["lstsq"]["gap"], rel=1e-6)


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_what_the_mesh_path_cannot_take_raises(child, name):
    msg = child["raised"][name]
    assert msg is not None, f"{REFUSED[name]} did not raise"
    assert "mesh path" in msg and "supports" in msg


def test_the_exchange_left_out_fails_the_tolerance(child):
    assert child["no_exchange"]["gap"] > TOL
