"""Every float32 contraction of the solvers states full precision.

On a TPU, XLA and Mosaic contract float32 operands in one bfloat16 pass
unless an operation asks for more, which leaves a "full precision" solve
at ~2^-9 relative accuracy.  The solvers therefore give every contraction
``Precision.HIGHEST`` themselves (``repro.kernels.common.matmul``/``vdot``
in XLA code, ``mxu_dot`` inside the kernels).  Here each entry point runs
at a tiny float32 size with every ``dot_general`` recorded as it is
traced, in the caller's thread and in worker threads alike; a float32
contraction at any other precision fails the test with the line that
made it.
"""
import threading
import traceback

import jax
import jax.numpy as jnp
import pytest
from jax.extend.core.primitives import dot_general_p

from repro.core import (
    SketchedSolver,
    generate_problem,
    lstsq,
    saa_sas_batch,
    sketched_lstsq,
)
from repro.kernels.common import HIGHEST
from repro.sharding import make_mesh

M, N = 512, 16
F32 = jnp.dtype(jnp.float32)


@pytest.fixture
def dots(monkeypatch):
    """Trace afresh and record (precision, call site) of each f32 dot."""
    seen, lock = [], threading.Lock()
    bind = dot_general_p.bind

    def recording_bind(*args, **params):
        if any(jnp.result_type(a) == F32 for a in args):
            frames = [
                f"{f.filename.rsplit('/src/', 1)[-1]}:{f.lineno}"
                for f in traceback.extract_stack()
                if "/src/repro/" in f.filename
            ]
            with lock:
                seen.append((params.get("precision"), frames[-1:]))
        return bind(*args, **params)

    jax.clear_caches()
    monkeypatch.setattr(dot_general_p, "bind", recording_bind)
    yield seen
    jax.clear_caches()


def _problem():
    p = generate_problem(jax.random.key(0), M, N, cond=1e3, dtype=jnp.float32)
    return p.A, p.b


def _run_service(A, b):
    from repro.serve import SolveService

    svc = SolveService(jax.random.key(3), max_delay_s=0.001)
    futs = [svc.submit(A, b, certified_rtol=1e-3, mode=mode)
            for mode in ("session", "bucket")]
    svc.flush()
    return [f.result(timeout=0) for f in futs]


def _run_stream(A, b):
    from repro.core import stream_lstsq
    from repro.cluster import ClusterSpec

    key = jax.random.key(4)
    stream_lstsq(A, b, key, method="saa", tile_rows=128)
    stream_lstsq(A, b, key, method="iterative", tile_rows=128)
    return lstsq(A, b, key, cluster=ClusterSpec(num_workers=2,
                                                checkpoint_every=0))


def _run_session(A, b):
    s = SketchedSolver(A, jax.random.key(5))
    s.solve(b)
    s.solve_many(jnp.stack([b, 2 * b], axis=1))
    s.certify()
    return s.update_rows(jnp.arange(4), A[:4] * 2)


ENTRY_POINTS = {
    "lstsq_auto": lambda A, b: lstsq(A, b, jax.random.key(1)),
    "lstsq_methods": lambda A, b: [
        lstsq(A, b, jax.random.key(1), method=m)
        for m in ("direct", "lsqr", "saa", "sap", "iterative", "fossils")
    ],
    "lstsq_kinds": lambda A, b: [
        lstsq(A, b, jax.random.key(1), method="iterative", sketch=k)
        for k in ("gaussian", "uniform_dense", "srht", "sparse_sign",
                  "uniform_sparse")
    ],
    "lstsq_certified_mixed_fused": lambda A, b: lstsq(
        A, b, jax.random.key(1), accuracy="certified", precision="mixed",
        fused=True,
    ),
    "lstsq_pallas": lambda A, b: [
        lstsq(A, b, jax.random.key(1), method="iterative", sketch=k,
              backend="pallas")
        for k in ("gaussian", "srht", "clarkson_woodruff")
    ],
    "saa_sas_batch": lambda A, b: saa_sas_batch(
        A, jnp.stack([b, -b], axis=1), jax.random.key(2)
    ),
    "sketched_lstsq": lambda A, b: sketched_lstsq(
        A, b, jax.random.key(2), mesh=make_mesh((1,), ("data",))
    ),
    "session": _run_session,
    "serve": _run_service,
    "stream_and_cluster": _run_stream,
}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_f32_contractions_are_full_precision(dots, entry):
    A, b = _problem()
    jax.block_until_ready(ENTRY_POINTS[entry](A, b))
    assert dots, "no float32 contraction was traced"
    low = sorted({
        tuple(site) for prec, site in dots if prec != (HIGHEST, HIGHEST)
    })
    assert not low, f"float32 contractions below HIGHEST at {low}"
