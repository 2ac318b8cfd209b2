"""Distributed sketch-and-solve (the paper's technique at cluster scale).

The tall matrix A (m × n, m ≫ n) is **row-sharded** across a mesh axis (or a
tuple of axes, e.g. ``('pod', 'data')`` on the multi-pod production mesh).
Every scatter-family sketch (CountSketch, sparse-sign, uniform-sparse) is a
linear row map with per-row parameters, so each shard sketches its local
rows into the *global* s-bucket space and one ``psum`` reconstructs
``SA = Σᵢ S A_i`` **exactly** — communication is a single s×(n+1) all-reduce,
independent of m.  (That psum is the collective form of the associative
partial-sketch merge in ``repro.streaming.accumulate``.)  The small QR runs
replicated; LSQR then runs distributed with row-sharded u-space vectors and
psum-reduced inner products (injected via ``lsqr(udot=...)``).

The sketch is the shared ``repro.core.sketch`` operator of the requested
kind: sampled ONCE at global size from ``key``, then its per-row parameter
arrays row-shard with A — each shard rewraps its slice into a local
operator of the same kind and calls the same backend-dispatched ``apply``
(reference segment_sum or the Pallas scatter-add kernel, per
``backend=``).  Note the draw is NOT bit-identical to ``saa_sas(key)``'s:
that solver derives its sketch key via ``split(key, 3)`` (it also needs
perturbation/norm keys for the fallback).

This is the native multi-pod form of SAA-SAS: compute scales 1/P, the
collective term is O(s·n) per solve + O(n) per LSQR iteration.

``lstsq`` routes here when A's ``NamedSharding`` splits its rows
(:func:`row_sharding`).  Inside the compiled solve the local applies and
their psums are named ``sketch`` and the LSQR loop ``lsqr``
(``jax.named_scope``, as a profile's ``tf_op`` path shows them); each call
is a ``repro.obs`` span ``sketched_lstsq`` (chips, m, n, itn) while tracing.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import backend as backend_lib
from . import linop
from . import sketch as sketch_lib
from ..kernels.common import vdot
from ..obs import trace as obs_trace
from .lsqr import lsqr
from .precond import SketchedFactor, default_sketch_size
from .result import SolveResult

__all__ = ["sketched_lstsq", "DistributedLSQResult", "shard_rows", "row_sharding"]

# Superseded by the unified result type.  The alias keeps attribute access
# working; field order/arity changed (arnorm, used_fallback... added), so
# positional unpacking of the old 4-tuple is not preserved.
DistributedLSQResult = SolveResult


def shard_rows(mesh, axes, A, b):
    """Place (A, b) row-sharded over ``axes`` of ``mesh``."""
    A = jax.device_put(A, NamedSharding(mesh, P(axes, None)))
    b = jax.device_put(b, NamedSharding(mesh, P(axes)))
    return A, b


def mesh_size(mesh, axes) -> int:
    """Devices along ``axes`` of ``mesh``: the shards of A's rows."""
    return math.prod(mesh.shape[a] for a in axes)


def row_sharding(A):
    """``(mesh, axes)`` where ``A`` is a 2-D array laid out by a
    ``NamedSharding`` that splits its rows over more than one device and
    keeps its columns whole; else None (one device, a replicated or
    column-split layout, a sparse matrix or operator, a traced value)."""
    sharding = getattr(A, "sharding", None)
    if not isinstance(sharding, NamedSharding) or len(A.shape) != 2:
        return None
    spec = tuple(sharding.spec) + (None, None)
    rows, cols = spec[0], spec[1]
    if cols not in (None, ()) or rows in (None, ()):
        return None
    axes = (rows,) if isinstance(rows, str) else tuple(rows)
    if mesh_size(sharding.mesh, axes) < 2:
        return None
    return sharding.mesh, axes


# Scatter-family kinds: per-row parameter arrays (field names) and the axis
# along which those arrays index rows of A — the axis that shards with A.
_ROW_PARAM_FIELDS = {
    sketch_lib.CountSketch: (("buckets", "signs"), 0),
    sketch_lib.UniformSparseSketch: (("buckets", "values"), 0),
    sketch_lib.SparseSignSketch: (("buckets", "signs"), 1),
}
ROW_SKETCHES = frozenset(_ROW_PARAM_FIELDS)


def sketched_lstsq(
    A,
    b: jax.Array,
    key: jax.Array,
    *,
    mesh,
    axes=("data",),
    sketch: str = "clarkson_woodruff",
    sketch_size: int | None = None,
    atol: float = 0.0,
    btol: float = 0.0,
    steptol: float | None = None,
    iter_lim: int = 100,
    backend: str = "auto",
) -> SolveResult:
    """Distributed SAA-SAS.  ``A``/``b`` must be row-sharded over ``axes``.

    Jit-compatible; lowers to one psum of the s×(n+1) sketch + one psum per
    LSQR iteration (n-vector + 3 scalars).  ``backend`` selects the local
    sketch-apply implementation (see ``repro.core.backend``).

    ``sketch`` may be any scatter-family kind (``clarkson_woodruff`` /
    ``countsketch``, ``sparse_sign``, ``uniform_sparse``) — their per-row
    parameter arrays shard with A, so each shard's slice is itself a valid
    operator into the global bucket space.  The dense-S kinds and SRHT have
    no row-local parameters (S columns or the Hadamard coupling would have
    to replicate); use the single-host or streaming drivers for those.

    The row-sharded shard_map layout needs A's entries on-device, so
    non-dense inputs (BCOO, materializable operators) are densified here;
    dense arrays pass through untouched, preserving their placement.
    Non-materializable operators are rejected — use the single-host
    matrix-free solvers for those.
    """
    A = linop.ensure_dense(A, who="the distributed row-sharded driver")
    if isinstance(axes, str):
        axes = (axes,)
    m, n = A.shape
    cls = sketch_lib.SKETCH_KINDS.get(sketch)
    if cls is None:
        raise ValueError(
            f"unknown sketch kind {sketch!r}; have "
            f"{sorted(sketch_lib.SKETCH_KINDS)}"
        )
    if cls not in ROW_SKETCHES:
        raise ValueError(
            f"sketch {sketch!r} has no per-row parameters to shard; the "
            "distributed driver supports the scatter kinds "
            "(clarkson_woodruff/countsketch, sparse_sign, uniform_sparse)"
        )
    if steptol is None:
        steptol = 32 * float(jnp.finfo(A.dtype).eps)
    with obs_trace.span("sketched_lstsq", chips=mesh_size(mesh, axes), m=m,
                        n=n) as sp:
        x, istop, itn, rnorm, arnorm = _solve(
            A, b, key, mesh=mesh, axes=tuple(axes), sketch=sketch,
            s=sketch_size if sketch_size is not None
            else default_sketch_size(n, m),
            atol=float(atol), btol=float(btol), steptol=float(steptol),
            iter_lim=int(iter_lim), backend=backend_lib.resolve(backend).name,
        )
        obs_trace.maybe_block(x)
        if sp:
            sp.set(itn=int(itn))
    return SolveResult(
        x=x, istop=istop, itn=itn, rnorm=rnorm, arnorm=arnorm,
        used_fallback=jnp.asarray(False),
        # a name cannot leave a jit: under one the caller's driver sets it
        method=None if isinstance(x, jax.core.Tracer) else "saa",
    )


# One compiled program per (mesh, axes, sketch, size, tolerances, backend):
# repeated solves with the same settings reuse it.
@partial(
    jax.jit,
    static_argnames=(
        "mesh", "axes", "sketch", "s", "atol", "btol", "steptol",
        "iter_lim", "backend",
    ),
)
def _solve(A, b, key, *, mesh, axes, sketch, s, atol, btol, steptol,
           iter_lim, backend):
    m, n = A.shape
    cls = sketch_lib.SKETCH_KINDS[sketch]
    # One global operator draw, shared by every shard; its per-row
    # parameter arrays row-shard with A.
    op = cls.sample(key, s, m, dtype=A.dtype)
    fields, row_axis = _ROW_PARAM_FIELDS[cls]
    params = tuple(getattr(op, f) for f in fields)
    param_spec = P(axes) if row_axis == 0 else P(None, axes)

    def local_solve(A_i, b_i, *params_i):
        # --- sketch locally into global bucket space, psum to assemble ----
        # Each shard's rows form a valid scatter sketch into the SAME
        # s-bucket space: rewrap the local parameter slices and reuse the
        # operator's backend-dispatched apply.  (Only the static d/k
        # metadata is read off the global op — its arrays are all replaced,
        # so nothing m-sized is captured replicated.)
        local_op = dataclasses.replace(
            op, m=A_i.shape[0], **dict(zip(fields, params_i))
        )
        with jax.named_scope("sketch"):
            SA = lax.psum(local_op.apply(A_i, backend=backend), axes)
            Sb = lax.psum(local_op.apply(b_i, backend=backend), axes)

        # --- replicated small factorization -------------------------------
        factor = SketchedFactor.from_sketch(SA)
        z0 = factor.warm_start(Sb)

        # --- distributed LSQR on Y = A R⁻¹ (operator form) ----------------
        # mv touches only local rows; rmv psums the shard contributions
        # (R is replicated and the triangular solve is linear, so solving
        # per-shard then psumming equals solving the psummed gradient).
        def mv(z):
            return factor.whiten_mv(A_i, z)

        def rmv(u):
            return lax.psum(factor.whiten_rmv(A_i, u), axes)

        def udot(u, w):
            return lax.psum(vdot(u, w), axes)

        with jax.named_scope("lsqr"):
            res = lsqr(
                mv, rmv, b_i, x0=z0, n=n, atol=atol, btol=btol,
                steptol=steptol, iter_lim=iter_lim, udot=udot,
            )
        x = factor.precondition(res.x)
        return x, res.istop, res.itn, res.rnorm, res.arnorm

    # Outputs are psum-fed, hence replicated; the replication check is off.
    return jax.shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes)) + (param_spec,) * len(params),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )(A, b, *params)
