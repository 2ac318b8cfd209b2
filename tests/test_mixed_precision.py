"""Mixed-precision sketching (precision="mixed") + tile autotuner.

Covers the PR's acceptance criteria:

- for ALL six sketch kinds, a bf16-sketched certified solve on the
  cond=1e8 problem reaches the SAME certified forward-error target as the
  fp32/f64 run — ``Certificate.passed`` both ways at an identical
  ``certified_rtol`` (the driver is allowed to escalate precision to get
  there; the certificate records whether it had to);
- at moderate conditioning the mixed run certifies WITHOUT escalating
  (``escalations == 0``, ``certificate.precision == "mixed"``) — the
  regime where the cheap sketch is free;
- kernel dtype contract: low-precision inputs come back in the f32
  accumulator dtype (never silently downcast);
- forcing a non-sketched method with precision="mixed" raises;
- the autotuner returns feasible block choices and the env kill-switch
  empties them.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import generate_problem, qr_solve
from repro.core import backend as backend_lib
from repro.core.lstsq import PRECISION_SUPPORT, lstsq
from repro.kernels import countsketch_apply, sketch_matmul
from repro.kernels.autotune import KINDS, best_blocks, predict_cost

ALL_KINDS = (
    "gaussian",
    "uniform_dense",
    "srht",
    "countsketch",
    "sparse_sign",
    "uniform_sparse",
)

RTOL = 1e-6  # shared certified target for the full-vs-mixed comparison


@pytest.mark.slow
@pytest.mark.parametrize("kind", ALL_KINDS)
def test_mixed_certifies_at_full_precision_rtol(kind):
    """bf16 sketch + fp32 refinement reaches the fp64 certified floor."""
    prob = generate_problem(
        jax.random.key(0), 2048, 32, cond=1e8, beta=1e-10, method="fast"
    )
    A, b = prob.A, prob.b
    x_qr = qr_solve(A, b)
    key = jax.random.key(1)
    results = {}
    for precision in ("full", "mixed"):
        res = lstsq(
            A, b, key, accuracy="certified", sketch=kind,
            precision=precision, certified_rtol=RTOL,
        )
        cert = res.certificate
        assert cert is not None
        assert bool(cert.passed), (
            f"{kind}/{precision}: bound={float(cert.rel_error_bound):.3e}"
        )
        assert float(cert.rel_error_bound) <= RTOL
        # the posterior bound is backed by the TRUE error
        err = float(jnp.linalg.norm(res.x - x_qr) / jnp.linalg.norm(x_qr))
        assert err <= RTOL
        results[precision] = cert
    # the mixed run may have repaired itself back to full precision — the
    # certificate must SAY so rather than silently passing
    assert results["full"].precision == "full"
    assert results["mixed"].precision in ("mixed", "full")


def test_mixed_moderate_cond_stays_mixed():
    """Where bf16 rounding is harmless, no escalation happens at all."""
    prob = generate_problem(
        jax.random.key(2), 2048, 32, cond=1e3, beta=1e-8, method="fast"
    )
    res = lstsq(
        prob.A, prob.b, jax.random.key(3), accuracy="certified",
        precision="mixed",
    )
    cert = res.certificate
    assert bool(cert.passed)
    assert int(cert.escalations) == 0
    assert cert.precision == "mixed"


def test_forced_unsupported_method_raises():
    A = jnp.ones((64, 4))
    b = jnp.ones(64)
    with pytest.raises(ValueError, match="precision"):
        lstsq(A, b, jax.random.key(0), method="lsqr", precision="mixed")
    assert "lsqr" not in PRECISION_SUPPORT


def test_kernels_keep_accumulator_dtype():
    """bf16 inputs return f32 (the mixed contract: no silent downcast)."""
    m, n, d = 512, 32, 128
    A = jax.random.normal(jax.random.key(4), (m, n), jnp.bfloat16)
    buckets = jax.random.randint(jax.random.key(5), (m,), 0, d)
    signs = jax.random.rademacher(jax.random.key(6), (m,), jnp.bfloat16)
    out = countsketch_apply(A, buckets, signs, d, interpret=True)
    assert out.dtype == jnp.float32
    S = jax.random.normal(jax.random.key(7), (d, m), jnp.bfloat16)
    out2 = sketch_matmul(S, A, interpret=True)
    assert out2.dtype == jnp.float32


def test_precisions_registry():
    assert backend_lib.PRECISIONS == ("full", "mixed")
    with pytest.raises(ValueError, match="precision"):
        lstsq(jnp.ones((8, 2)), jnp.ones(8), jax.random.key(0),
              precision="half")


# --------------------------------------------------------------------------
# Autotuner
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_best_blocks_feasible(kind):
    """Winners exist, carry exactly the kind's knobs, and cost finitely."""
    blocks = best_blocks(kind, 16384, 128, 512, "float32", device="TPU v5 lite")
    assert set(blocks) == set(KINDS[kind])
    assert all(isinstance(v, int) and v > 0 for v in blocks.values())
    cost = predict_cost(kind, 16384, 128, 512, "float32", blocks, "TPU v5 lite")
    assert 0 < cost < float("inf")


def test_countsketch_is_not_tuned():
    """The CountSketch kernel's blocks follow from its shape: the tuner has
    no such kind, and the committed cache no such entry."""
    from repro.kernels import autotune

    for kind in ("countsketch", "clarkson_woodruff"):
        with pytest.raises(ValueError, match="unknown autotune kind"):
            best_blocks(kind, 4096, 64, 256, "float32", device="TPU v5 lite")
    assert not [k for k in autotune._load_cache() if k.startswith("countsketch")]


def test_best_blocks_alias_and_cache_consistency():
    a = best_blocks("uniform_dense", 8192, 64, 256, "float32",
                    device="TPU v5 lite")
    b = best_blocks("sketch_matmul", 8192, 64, 256, "float32",
                    device="TPU v5 lite")
    assert a == b


def test_best_blocks_cache_miss_warns_once(caplog):
    """An unseen key falls back to model blocks with ONE warning naming
    them; repeats stay silent, and the fallback equals the cost model."""
    from repro.kernels import autotune

    # an off-sweep shape no committed cache will ever contain
    args = ("sketch_matmul", 12345, 67, 321, "float32")
    device = "TPU v5 lite"
    key = autotune._key(*args, device=device)
    autotune._MISS_WARNED.discard(key)
    with caplog.at_level("WARNING", logger="repro.kernels.autotune"):
        blocks = best_blocks(*args, device=device)
    hits = [r for r in caplog.records if key in r.getMessage()]
    assert len(hits) == 1
    assert "fallback" in hits[0].getMessage() or "falling back" in hits[0].getMessage()
    assert str(blocks) in hits[0].getMessage()
    assert blocks == dict(autotune._model_best(*args[:4], "float32", device))

    caplog.clear()
    with caplog.at_level("WARNING", logger="repro.kernels.autotune"):
        again = best_blocks(*args, device=device)
    assert again == blocks
    assert not [r for r in caplog.records if key in r.getMessage()]


def test_best_blocks_cache_hit_does_not_warn(caplog):
    """Committed-cache hits never touch the warning path."""
    from repro.kernels import autotune

    cached = autotune._load_cache()
    if not cached:
        pytest.skip("no committed autotune cache in this checkout")
    key = next(iter(cached))
    kind, m, n, d, dtype, device = key.split("|")
    m, n, d = (int(s.split("=")[1]) for s in (m, n, d))
    with caplog.at_level("WARNING", logger="repro.kernels.autotune"):
        best_blocks(kind, m, n, d, dtype, device=device)
    assert not [r for r in caplog.records if "cache miss" in r.getMessage()]


def test_kernel_blocks_env_kill_switch(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert backend_lib.kernel_blocks("gaussian", 4096, 64, 256,
                                     "float32", interpret=True) == {}
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    blocks = backend_lib.kernel_blocks("gaussian", 4096, 64, 256,
                                       "float32", interpret=True)
    assert isinstance(blocks, dict)


def test_tuner_refuses_unknown_device():
    """No peak table, no model: the tuner raises, and kernel_blocks lets
    it through (the compiled path on a CPU host names the CPU)."""
    from repro.kernels import autotune

    with pytest.raises(ValueError, match="peak table"):
        best_blocks("sketch_matmul", 12345, 67, 321, "float32",
                    device="nonexistent_device")
    with pytest.raises(ValueError, match="peak table"):
        autotune.predict_cost("sketch_matmul", 4096, 64, 256, "float32", {},
                              "cpu")
    with pytest.raises(ValueError, match="peak table"):
        backend_lib.kernel_blocks("sketch_matmul", 12345, 67, 321, "float32",
                                  interpret=False)


def test_resolve_fused_env(monkeypatch):
    monkeypatch.delenv("REPRO_FUSED_QR", raising=False)
    assert backend_lib.resolve_fused(None) is False
    assert backend_lib.resolve_fused(True) is True
    assert backend_lib.resolve_fused(False) is False
    monkeypatch.setenv("REPRO_FUSED_QR", "1")
    assert backend_lib.resolve_fused(None) is True
