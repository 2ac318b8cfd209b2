"""The solver names its layers inside its jitted programs.

``refine`` (the heavy-ball loop), ``lsqr`` (the service's batched LSQR and
the row-sharded solve's distributed LSQR), ``sketch`` (the row-sharded
solve's local sketch applies and their psums) and ``certify`` (the
service's blocked certificate) are ``jax.named_scope``
components of the ops' locations, which XLA keeps as their ``op_name``
metadata and a profile shows beside each device op.  A scope is matched as
a whole path component: ``lsqr.<locals>.body`` is not ``lsqr``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed
from repro.core.iterative import iterative_sketching
from repro.core.session import SketchedSolver, _solve_many
from repro.serve.service import _certify_batch


def _paths(lowered) -> set:
    text = lowered.as_text(debug_info=True)
    return {tuple(p.split("/")) for p in re.findall(r'loc\("([^"]*)"', text)}


def _under(paths, scope) -> list:
    return [p for p in paths if scope in p]


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((512, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(512), jnp.float32)
    return A, b, jax.random.key(0)


@pytest.fixture(scope="module")
def session(problem):
    A, b, key = problem
    return SketchedSolver(A, key), jnp.stack([b, 2 * b], axis=1)


def test_refine_scope_in_iterative_sketching(problem):
    A, b, key = problem
    paths = _paths(jax.jit(
        lambda A, b, k: iterative_sketching(A, b, k).x).lower(A, b, key))
    refine = _under(paths, "refine")
    assert any("while" in p for p in refine)  # the loop itself
    assert any("body" in p for p in refine)  # and the ops it runs


def test_lsqr_scope_in_solve_many(session):
    s, B = session
    paths = _paths(_solve_many.lower(
        s._solve_op, s._Y, s.factor, s._sketch_op, B, **s._kw,
        history=False))
    lsqr = _under(paths, "lsqr")
    assert any("while" in p for p in lsqr)
    # lsqr's own function names hold the word but are no scope
    assert any(c.startswith("lsqr.") for p in paths for c in p)
    assert not _under(paths, "refine") and not _under(paths, "certify")


def test_certify_scope_in_certify_batch(session):
    s, B = session
    X = jnp.zeros((s.A.shape[1], B.shape[1]), B.dtype)
    paths = _paths(_certify_batch.lower(
        s._solve_op, s.factor, B, X, 0.5, 1.0, 0.5))
    certify = _under(paths, "certify")
    assert any(any(c.startswith("jit(_solve_triangular") for c in p)
               for p in certify)


def test_lsqr_and_sketch_scopes_in_the_row_sharded_solve(problem):
    from repro.sharding import make_mesh

    A, b, key = problem
    mesh = make_mesh((1,), ("data",))
    paths = _paths(distributed._solve.lower(
        A, b, key, mesh=mesh, axes=("data",), sketch="clarkson_woodruff",
        s=64, atol=0.0, btol=0.0, steptol=1e-6, iter_lim=10,
        backend="reference"))
    lsqr = _under(paths, "lsqr")
    assert any("while" in p for p in lsqr)  # the loop, its psums inside
    sketch = _under(paths, "sketch")
    assert sketch and not any("while" in p for p in sketch)
    assert not [p for p in lsqr if "sketch" in p]
