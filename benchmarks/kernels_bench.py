"""Backend sweep: the Pallas sketch kernels measured END-TO-END.

The paper's headline speedup lives or dies on the sketch apply inside the
full solve, so this bench no longer times kernels in isolation: for every
kernel-backed sketch kind it runs ``saa_sas`` twice — ``backend="reference"``
(pure-jnp applies) vs ``backend="pallas"`` (the ``repro.kernels`` ops) — and
reports both, plus the analytically-derived TPU roofline terms of the apply.

On this CPU container the pallas rows execute in ``interpret=True`` mode, so
their wall times measure the *kernel semantics*, not TPU performance; the
``derived`` column's HBM bytes / MXU flops / v5e roofline times are the
numbers the §Perf log tracks.

Two PR 6 sweeps ride along:

- **fused vs unfused** — ``sketch_qr`` (sketch feeding shifted-CholeskyQR3
  directly, BLAS3-rate finish, fused Gram on the pallas backend) against
  the seed pipeline ``op.apply_op`` → ``jnp.linalg.qr`` (Householder).
  Measured on the reference backend so the wall times are real compute,
  not interpret-mode overhead; the acceptance row is the largest shape.
- **bf16 vs fp32 sketch** — full certified solves with
  ``precision="mixed"`` vs ``"full"``, reporting wall time AND the
  certified forward-error bound, plus the true error vs QR: the claim
  under test is that the cheap sketch loses *no certified accuracy*.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import SketchedFactor, generate_problem, resolve_backend, saa_sas
from repro.core.lstsq import lstsq
from repro.core.precond import _sketch_apply
from repro.core.sketch import sample as sample_sketch
from repro.kernels.tsqr import sketch_qr
from repro.launch.mesh import HW

from .common import emit, time_fn

BACKENDS = ("reference", "pallas")
KINDS = ("countsketch", "srht", "gaussian", "uniform_dense")

# (m, n) sweep for fused-vs-unfused; the LAST entry is the acceptance shape.
# Tall-skinny (m ≫ n) is the paper's regime and the one the fused pipeline
# targets: at fat aspect ratios the O(m·n·d) apply dominates both pipelines
# equally and the ratio degenerates to 1.0.
FUSED_SHAPES = ((4096, 64), (8192, 64), (16384, 128), (32768, 128))
FUSED_KINDS = ("countsketch", "srht", "gaussian", "uniform_dense")


def _derived_apply_terms(kind: str, m: int, n: int, d: int) -> str:
    """Roofline terms of ONE sketch apply S·[A|b] at v5e constants."""
    nn = n + 1  # the solvers sketch A and b
    if kind == "countsketch":
        hbm = (m * nn + d * nn) * 4 + m * 8
        flops = m * nn  # one signed add per entry, no MXU pass
    elif kind == "srht":
        m_pad = 1 << (m - 1).bit_length()
        c = min(1024, m_pad)
        r = m_pad // c
        hbm = 2 * (m_pad * nn * 4) * 2 + d * nn * 4  # two streamed passes
        flops = 2 * m_pad * nn * (r + c)
    elif kind == "gaussian":
        # fused-PRNG: S never touches HBM
        hbm = (m * nn + d * nn) * 4
        flops = 2 * m * d * nn
    else:  # uniform_dense: materialized S streamed from HBM
        hbm = (d * m + m * nn + d * nn) * 4
        flops = 2 * m * d * nn
    t_mem = hbm / HW["hbm_bw"]
    t_mxu = flops / HW["peak_flops_bf16"]
    bound = "mem" if t_mem > t_mxu else "mxu"
    return (
        f"hbm_bytes={hbm};mxu_flops={flops};"
        f"v5e_mem_us={t_mem*1e6:.1f};v5e_mxu_us={t_mxu*1e6:.1f};bound={bound}"
    )


def _fused_sweep(seed=0):
    """Fused ``sketch_qr`` vs unfused apply → Householder QR, per kind/shape.

    Reference-backend wall times (real compute on this host; interpret-mode
    pallas wall times say nothing about TPU perf).  The fused pipeline is
    compiled as ONE computation — ``jax.jit`` around the whole
    apply → Gram → shifted-CholeskyQR3 chain, so XLA fuses the stages and
    B=SA never round-trips between dispatches — against the seed pipeline's
    two staged steps (``op.apply_op`` then LAPACK Householder QR), which is
    exactly the fused/unfused distinction.  Wins are largest in the paper's
    tall-skinny regime where the (s, n) QR and the apply's elementwise
    pre/post stages (SRHT's D-scale + gather, CountSketch's scatter) are a
    real fraction of the pipeline.
    """
    for m, n in FUSED_SHAPES:
        d = 4 * n
        A = jax.random.normal(jax.random.key(seed), (m, n), jnp.float64)
        for kind in FUSED_KINDS:
            op = sample_sketch(kind, jax.random.key(seed + 1), d, m)

            def unfused():
                B = op.apply_op(A, backend="reference")
                Q, R = jnp.linalg.qr(B, mode="reduced")
                return Q, R

            @jax.jit
            def fused(A):
                Q, R, _ = sketch_qr(op, A, backend="reference")
                return Q, R

            t_unfused = time_fn(lambda: unfused()[1])
            t_fused = time_fn(lambda: fused(A)[1])
            # correctness guard: |R| must agree up to row signs
            R_u = jnp.abs(unfused()[1])
            R_f = jnp.abs(fused(A)[1])
            rdiff = float(jnp.linalg.norm(R_u - R_f) / jnp.linalg.norm(R_u))
            emit(
                f"fused_qr/{kind}/m{m}_n{n}/unfused", t_unfused,
                f"m={m};n={n};d={d};pipeline=apply+householder",
            )
            emit(
                f"fused_qr/{kind}/m{m}_n{n}/fused", t_fused,
                f"m={m};n={n};d={d};pipeline=sketch_qr;"
                f"speedup={t_unfused / t_fused:.2f}x;Rdiff={rdiff:.1e}",
            )


def _mixed_sweep(seed=0, m=8192, n=64):
    """Certified solves, fp32-throughout vs bf16 sketch + fp32 refinement.

    Moderate conditioning (the regime mixed precision targets — at extreme
    cond the certified driver escalates back to full precision and the two
    columns converge).  Reports wall time, the posterior certified bound
    AND the true forward error vs QR, per sketch precision.
    """
    from repro.core import qr_solve

    prob = generate_problem(
        jax.random.key(seed), m, n, cond=1e4, beta=1e-8, method="fast"
    )
    A, b = prob.A, prob.b
    x_qr = qr_solve(A, b)
    xnorm = float(jnp.linalg.norm(x_qr))
    key = jax.random.key(seed + 1)
    for precision in ("full", "mixed"):
        def solve(precision=precision):
            return lstsq(A, b, key, accuracy="certified", precision=precision)

        t = time_fn(lambda: solve().x)
        res = solve()
        cert = res.certificate
        err = float(jnp.linalg.norm(res.x - x_qr)) / max(xnorm, 1e-300)
        emit(
            f"mixed/certified/{precision}", t,
            f"m={m};n={n};relerr={err:.3e};"
            f"bound={float(cert.rel_error_bound):.3e};"
            f"passed={int(bool(cert.passed))};esc={cert.escalations};"
            f"final_precision={cert.precision}",
        )

    # the raw sketch-apply cost the bf16 path is buying down, per kind
    for kind in FUSED_KINDS:
        d = 4 * n
        Af = A.astype(jnp.float32)
        op = sample_sketch(kind, jax.random.key(seed + 2), d, m, dtype=jnp.float32)
        t_full = time_fn(
            lambda: _sketch_apply(op, Af, backend="reference", precision="full")
        )
        t_mixed = time_fn(
            lambda: _sketch_apply(op, Af, backend="reference", precision="mixed")
        )
        emit(
            f"mixed/apply/{kind}", t_mixed,
            f"full_s={t_full:.3e};mixed_over_full="
            f"{t_mixed / max(t_full, 1e-12):.2f}x;"
            f"note=reference_backend_cast_cost_only;"
            f"tpu_bf16_mxu_rate=2x_fp32",
        )


def run(seed=0, m=8192, n=128):
    prob = generate_problem(
        jax.random.key(seed), m, n, cond=1e10, beta=1e-10, method="fast"
    )
    A, b = prob.A, prob.b
    key = jax.random.key(seed + 1)

    for kind in KINDS:
        d = 4 * n
        derived = _derived_apply_terms(kind, m, n, d)
        times = {}
        for backend in BACKENDS:
            rb = resolve_backend(backend)
            t = time_fn(
                lambda: saa_sas(
                    A, b, key, sketch=kind, sketch_size=d, backend=backend
                ).x
            )
            times[backend] = t
            r = saa_sas(A, b, key, sketch=kind, sketch_size=d, backend=backend)
            emit(
                f"e2e/saa_sas/{kind}/{backend}",
                t,
                f"backend={rb.name};interpret={int(rb.interpret)};"
                f"itn={int(r.itn)};m={m};n={n};d={d};{derived}",
            )
        emit(
            f"e2e/saa_sas/{kind}/ratio",
            times["pallas"],
            f"pallas_over_reference={times['pallas']/times['reference']:.2f}x"
            f";note=interpret-mode_wall_times_not_TPU_perf",
        )

    _fused_sweep(seed=seed)
    _mixed_sweep(seed=seed)
