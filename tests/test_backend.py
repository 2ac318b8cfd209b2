"""Backend policy + reference/pallas parity, tested THROUGH the solvers.

Per-kernel allclose lives in test_kernels.py; these tests assert the thing
the paper's speedup claim actually needs — that swapping the sketch-apply
backend under ``saa_sas`` / ``sap_sas`` / ``sketched_lstsq`` leaves the
solve unchanged to solver tolerance (backend numerics exercised end-to-end,
not per-kernel).  On this CPU container "pallas" runs in interpret mode.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    generate_problem,
    saa_sas,
    saa_sas_batch,
    sap_sas,
    sample_sketch,
    sketched_lstsq,
)
from repro.core.backend import (
    BACKENDS,
    KERNEL_BACKED_KINDS,
    default_interpret,
    kernel_backed,
    resolve,
)
from repro.core.distributed import shard_rows

# Every kind whose apply dispatches to a Pallas kernel (alias included once).
KERNEL_KINDS = sorted(KERNEL_BACKED_KINDS - {"clarkson_woodruff"})


@pytest.fixture(scope="module")
def prob():
    # m a power of two keeps the SRHT pad-free; modest size keeps the
    # interpret-mode kernels fast.
    return generate_problem(jax.random.key(0), 1024, 24, cond=1e8, beta=1e-10)


def relerr(x, ref):
    return float(jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref))


# --------------------------------------------------------------------------
# Policy
# --------------------------------------------------------------------------


def test_resolve_policy(monkeypatch):
    monkeypatch.delenv("REPRO_SKETCH_BACKEND", raising=False)
    assert resolve("auto", platform="tpu").name == "pallas"
    assert not resolve("auto", platform="tpu").interpret
    assert resolve("auto", platform="cpu").name == "reference"
    assert resolve("pallas", platform="cpu").interpret
    assert resolve("pallas", platform="tpu").interpret is False
    assert resolve("reference", platform="cpu").name == "reference"
    with pytest.raises(ValueError):
        resolve("numpy")


def test_resolve_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_SKETCH_BACKEND", "pallas")
    assert resolve("auto", platform="cpu").name == "pallas"
    # explicit knob beats the env var
    assert resolve("reference", platform="cpu").name == "reference"
    monkeypatch.setenv("REPRO_SKETCH_BACKEND", "nope")
    with pytest.raises(ValueError):
        resolve("auto", platform="cpu")


def test_default_interpret():
    assert default_interpret("cpu") and default_interpret("gpu")
    assert not default_interpret("tpu")


def test_compiled_kernel_refuses_float64():
    """float64 reaching a compiled kernel raises at dispatch (trace time),
    naming the fix — no Mosaic traceback, no silent reroute."""
    from repro.kernels import countsketch_apply

    A = jax.ShapeDtypeStruct((256, 8), jnp.float64)
    h = jax.ShapeDtypeStruct((256,), jnp.int32)
    s = jax.ShapeDtypeStruct((256,), jnp.float64)
    with pytest.raises(TypeError, match="float64.*reference"):
        jax.eval_shape(
            lambda A, h, s: countsketch_apply(A, h, s, 32, interpret=False),
            A, h, s,
        )


def test_interpret_mode_refused_on_tpu(monkeypatch):
    """With a TPU attached the kernels only run compiled."""
    from repro.core import backend as backend_lib
    from repro.kernels.common import resolve_interpret

    monkeypatch.setattr(backend_lib, "default_interpret", lambda p=None: False)
    assert resolve_interpret(None) is False
    with pytest.raises(ValueError, match="interpret=True"):
        resolve_interpret(True)


def test_kernel_backed_partition():
    assert kernel_backed("countsketch") and kernel_backed("clarkson_woodruff")
    assert kernel_backed("srht") and kernel_backed("gaussian")
    assert not kernel_backed("sparse_sign") and not kernel_backed("uniform_sparse")
    assert "auto" in BACKENDS and "reference" in BACKENDS and "pallas" in BACKENDS


# --------------------------------------------------------------------------
# Operator-level parity (the same linear map S on both backends)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KERNEL_KINDS)
def test_operator_apply_backend_parity(kind):
    m, n, d = 300, 9, 64
    op = sample_sketch(kind, jax.random.key(3), d, m)
    A = jax.random.normal(jax.random.key(4), (m, n))
    ref = op.apply(A, backend="reference")
    pal = op.apply(A, backend="pallas")
    assert jnp.allclose(ref, pal, rtol=1e-9, atol=1e-9)
    # vector apply (the Sb path of the solvers)
    b = jax.random.normal(jax.random.key(5), (m,))
    assert jnp.allclose(
        op.apply(b, backend="reference"), op.apply(b, backend="pallas"),
        rtol=1e-9, atol=1e-9,
    )


@pytest.mark.parametrize("kind", ["sparse_sign", "uniform_sparse"])
def test_kernel_less_kinds_fall_back(kind):
    """Kinds without a kernel accept backend="pallas" (reference fallback)."""
    op = sample_sketch(kind, jax.random.key(6), 32, 200)
    A = jax.random.normal(jax.random.key(7), (200, 4))
    assert jnp.array_equal(op.apply(A, backend="pallas"), op.apply(A, backend="reference"))


# --------------------------------------------------------------------------
# Solver-level parity (ISSUE acceptance: through the full solve)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KERNEL_KINDS)
def test_saa_backend_parity(prob, kind):
    r_ref = saa_sas(prob.A, prob.b, jax.random.key(1), sketch=kind, backend="reference")
    r_pal = saa_sas(prob.A, prob.b, jax.random.key(1), sketch=kind, backend="pallas")
    assert r_ref.converged and r_pal.converged
    assert relerr(r_ref.x, prob.x_true) < 1e-6
    assert relerr(r_pal.x, prob.x_true) < 1e-6
    assert relerr(r_pal.x, r_ref.x) < 1e-6


@pytest.mark.parametrize("kind", ["countsketch", "srht"])
def test_saa_backend_parity_operator_form(prob, kind):
    """materialize_y=False (the at-scale form) must show the same parity."""
    kw = dict(sketch=kind, materialize_y=False)
    r_ref = saa_sas(prob.A, prob.b, jax.random.key(1), backend="reference", **kw)
    r_pal = saa_sas(prob.A, prob.b, jax.random.key(1), backend="pallas", **kw)
    assert r_ref.converged and r_pal.converged
    assert relerr(r_pal.x, r_ref.x) < 1e-6


def test_sap_accepts_backend(prob):
    # SAP is the paper's negative result: no dimension reduction, so the
    # preconditioned-but-full-size LSQR amplifies accumulation-order noise
    # between backends ~κ-fold.  Parity here is necessarily looser than
    # SAA's (which whitens before iterating).
    r_ref = sap_sas(prob.A, prob.b, jax.random.key(2), backend="reference")
    r_pal = sap_sas(prob.A, prob.b, jax.random.key(2), backend="pallas")
    assert relerr(r_pal.x, r_ref.x) < 1e-2


def test_sketched_lstsq_accepts_backend(prob):
    """Distributed solver on a 1-device mesh: backend parity in-process
    (multi-device correctness is covered by test_multidevice.py)."""
    mesh = jax.make_mesh((1,), ("data",))
    A, b = shard_rows(mesh, ("data",), prob.A, prob.b)
    r_ref = sketched_lstsq(A, b, jax.random.key(1), mesh=mesh, backend="reference")
    r_pal = sketched_lstsq(A, b, jax.random.key(1), mesh=mesh, backend="pallas")
    assert relerr(r_ref.x, prob.x_true) < 1e-6
    assert relerr(r_pal.x, r_ref.x) < 1e-6
    # both solvers converge to the LS solution (their sketch draws differ:
    # saa_sas derives its sketch key via split(key, 3))
    r_saa = saa_sas(
        prob.A, prob.b, jax.random.key(1), materialize_y=False, backend="reference"
    )
    assert relerr(r_ref.x, r_saa.x) < 1e-6


def test_sketched_lstsq_compiles_once_per_setting(prob):
    """Repeated solves with the same settings reuse one compiled program;
    a new key is data, not a new program."""
    from repro.core import distributed
    from repro.sharding import make_mesh

    mesh = make_mesh((1,), ("data",))
    A, b = shard_rows(mesh, ("data",), prob.A, prob.b)
    before = distributed._solve._cache_size()
    for seed in (3, 4, 5):
        sketched_lstsq(A, b, jax.random.key(seed), mesh=mesh, iter_lim=57)
    assert distributed._solve._cache_size() == before + 1


# --------------------------------------------------------------------------
# Batched front-end
# --------------------------------------------------------------------------


def test_saa_batch_multi_rhs_matches_single(prob):
    k = 4
    noise = jax.random.normal(jax.random.key(8), (prob.b.shape[0], k - 1))
    B_rhs = jnp.concatenate([prob.b[:, None], prob.b[:, None] + 0.1 * noise], axis=1)
    res = saa_sas_batch(prob.A, B_rhs, jax.random.key(1))
    assert res.x.shape == (prob.A.shape[1], k)
    assert res.istop.shape == (k,)
    for j in range(k):
        single = saa_sas(prob.A, B_rhs[:, j], jax.random.key(1), use_fallback=False)
        assert relerr(res.x[:, j], single.x) < 1e-6


def test_saa_batch_multi_rhs_operator_form(prob):
    B_rhs = jnp.stack([prob.b, 2.0 * prob.b], axis=1)
    r_mat = saa_sas_batch(prob.A, B_rhs, jax.random.key(1), materialize_y=True)
    r_op = saa_sas_batch(prob.A, B_rhs, jax.random.key(1), materialize_y=False)
    assert relerr(r_op.x, r_mat.x) < 1e-5


def test_saa_batch_problem_batch(prob):
    A3 = jnp.stack([prob.A, 1.5 * prob.A])
    b2 = jnp.stack([prob.b, prob.b])
    res = saa_sas_batch(A3, b2, jax.random.key(1))
    assert res.x.shape == (2, prob.A.shape[1])
    assert relerr(res.x[0], prob.x_true) < 1e-6
    # scaling A by c scales the LS solution by 1/c
    assert relerr(res.x[1] * 1.5, prob.x_true) < 1e-6


def test_saa_batch_backend_parity(prob):
    B_rhs = jnp.stack([prob.b, 0.5 * prob.b], axis=1)
    r_ref = saa_sas_batch(prob.A, B_rhs, jax.random.key(1), backend="reference")
    r_pal = saa_sas_batch(prob.A, B_rhs, jax.random.key(1), backend="pallas")
    assert relerr(r_pal.x, r_ref.x) < 1e-6


def test_saa_batch_shape_validation(prob):
    with pytest.raises(ValueError):
        saa_sas_batch(prob.A, prob.b, jax.random.key(1))  # b must be (m, k)
    with pytest.raises(ValueError):
        saa_sas_batch(prob.A[None], prob.b[None, :100], jax.random.key(1))
