"""Per-kernel allclose vs ref.py oracles — shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (
    countsketch_apply, countsketch_ref,
    fused_gaussian_ref, fused_gaussian_sketch, gaussian_matrix_ref,
    hadamard_transform, residual_pass, residual_pass_ref, sketch_matmul,
    sketch_matmul_ref, srht_apply,
)
from repro.kernels.countsketch.ops import stored_by_columns
from repro.kernels.residual_pass.ops import _tile_rows
from repro.kernels.srht.ref import hadamard_ref, srht_ref


def _tol(dtype):
    return dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


def _buckets(m, d, skew):
    if skew:  # every row in one of three buckets
        pick = jax.random.randint(jax.random.key(2), (m,), 0, 3)
        return jnp.array([0, d // 2, d - 1], jnp.int32)[pick]
    return jax.random.randint(jax.random.key(2), (m,), 0, d, dtype=jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,d,skew", [
    (1000, 100, 64, False), (513, 7, 200, False), (4096, 256, 512, False),
    (300, 1, 33, False), (8, 128, 8, False),
    (4096, 64, 2000, False),  # many more buckets than one MXU tile
    (300, 40, 2000, False),  # most buckets empty
    (3000, 130, 700, True),  # heavy skew
    (2500, 150, 300, False),  # a partial last row tile
    (3000, 1, 1200, False),  # a vector
])
def test_countsketch(m, n, d, skew, dtype):
    A = jax.random.normal(jax.random.key(1), (m, n), dtype)
    h = _buckets(m, d, skew)
    s = jax.random.rademacher(jax.random.key(3), (m,), dtype)
    got = countsketch_apply(A, h, s, d, interpret=True).astype(jnp.float32)
    want = countsketch_ref(A.astype(jnp.float32), h, s.astype(jnp.float32), d)
    assert got.shape == want.shape
    assert jnp.allclose(got, want, **_tol(dtype))


def test_countsketch_vector_returns_a_vector():
    m, d = 2500, 300
    b = jax.random.normal(jax.random.key(1), (m,), jnp.float32)
    h = _buckets(m, d, False)
    s = jax.random.rademacher(jax.random.key(3), (m,), jnp.float32)
    got = countsketch_apply(b, h, s, d, interpret=True)
    assert got.shape == (d,)
    assert jnp.allclose(got, countsketch_ref(b, h, s, d), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m,n,by_columns", [
    (1 << 20, 1000, True), (1 << 20, 1024, False), (4000, 1000, False),
    (1 << 19, 64, True), (3000, 1, True), (129, 1000, False),
    (1000, 129, True), (4096, 256, False),
])
def test_countsketch_orientation_follows_the_tpu_layout(m, n, by_columns):
    """The kernel reads A by columns exactly where XLA's TPU layout (the
    tiling that pads less, rows on a tie) stores it so."""
    assert stored_by_columns(m, n) is by_columns


@pytest.mark.parametrize("d", [64, 2000])
def test_countsketch_reads_a_in_its_own_order(d):
    """The kernel adds each row into its bucket where it lies: no sort of
    the buckets and no gathered copy of A, whatever d is."""
    A = jax.ShapeDtypeStruct((4096, 256), jnp.float32)
    h = jax.ShapeDtypeStruct((4096,), jnp.int32)
    s = jax.ShapeDtypeStruct((4096,), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda A, h, s: countsketch_apply(A, h, s, d, interpret=True))(A, h, s))
    assert "sort" not in text and "gather" not in text


@pytest.mark.parametrize("m,n,tiles,near", [
    (9000, 200, "ragged", False),  # by columns
    (8192, 200, "whole", False),
    (5000, 1000, "ragged", False),  # by columns at fig3 width
    (1000, 200, "short", False),  # one tile, shorter than a full one
    (9000, 256, "ragged", False),  # by rows
    (300, 256, "short", False),
    (5000, 37, "ragged", False),  # n not a multiple of eight
    (5000, 1000, "ragged", True),  # near the least-squares solution
    (9000, 256, "ragged", True),
])
def test_residual_pass(m, n, tiles, near):
    """One pass gives Aᵀ(b − A x) and ‖b − A x‖² as the two products do,
    on either layout, with ragged and short m, and near convergence, where
    g is a small difference of large terms."""
    A = jax.random.normal(jax.random.key(1), (m, n), jnp.float32)
    b = jax.random.normal(jax.random.key(2), (m,), jnp.float32)
    if near:  # x rounded from the f64 solution: g is rounding noise
        x = jnp.linalg.lstsq(A.astype(jnp.float64), b.astype(jnp.float64))[0]
        x = x.astype(jnp.float32)
    else:
        x = jax.random.normal(jax.random.key(3), (n,), jnp.float32)
    tm = _tile_rows(m, n, stored_by_columns(m, n))
    assert tiles == ("short" if tm == m else "ragged" if m % tm else "whole")
    g, rsq = residual_pass(A, x, b, interpret=True)
    assert g.shape == (n,) and g.dtype == rsq.dtype == jnp.float32
    g64, rsq64 = residual_pass_ref(*(v.astype(jnp.float64) for v in (A, x, b)))
    g32, rsq32 = residual_pass_ref(A, x, b)  # the two f32 products
    assert abs(float(rsq) - float(rsq64)) <= 1e-5 * float(rsq64)
    err = float(jnp.linalg.norm(g - g64))
    if near:
        # no worse than XLA's two f32 products, which differ from the f64
        # gradient by far more than the gradient itself here
        err32 = float(jnp.linalg.norm(g32 - g64))
        assert float(jnp.linalg.norm(g64)) < err32
        assert err <= 2 * err32, (err, err32)
    else:
        assert err <= 1e-5 * float(jnp.linalg.norm(g64))


@pytest.mark.parametrize("m", [8, 64, 512, 2048, 8192])
@pytest.mark.parametrize("n", [1, 5, 130])
def test_hadamard(m, n):
    x = jax.random.normal(jax.random.key(m + n), (m, n), jnp.float32)
    got = hadamard_transform(x, interpret=True)
    want = hadamard_ref(x)
    assert jnp.allclose(got, want, rtol=2e-4, atol=2e-3 * m ** 0.5)


@pytest.mark.parametrize("m,n,d", [(1000, 37, 256), (4096, 128, 512)])
def test_srht(m, n, d):
    m_pad = 1 << (m - 1).bit_length()
    A = jax.random.normal(jax.random.key(0), (m, n), jnp.float32)
    signs = jax.random.rademacher(jax.random.key(1), (m_pad,), jnp.float32)
    rows = jax.random.choice(jax.random.key(2), m_pad, (d,), replace=False)
    got = srht_apply(A, signs, rows, d, interpret=True)
    want = srht_ref(A, signs, rows, d)
    assert jnp.allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d,m,n", [(64, 1000, 100), (200, 513, 7), (128, 2048, 1)])
def test_sketch_matmul(d, m, n, dtype):
    S = jax.random.normal(jax.random.key(1), (d, m), dtype)
    A = jax.random.normal(jax.random.key(2), (m, n), dtype)
    got = sketch_matmul(S, A, interpret=True).astype(jnp.float32)
    want = sketch_matmul_ref(S.astype(jnp.float32), A.astype(jnp.float32))
    tol = dict(rtol=5e-2, atol=2.0) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-3)
    assert jnp.allclose(got, want, **tol)


@pytest.mark.parametrize("d,m,n", [(64, 500, 33), (256, 1024, 130), (33, 100, 1)])
def test_fused_gaussian_bitwise_prng(d, m, n):
    """The in-kernel threefry must generate the SAME S as the jnp oracle."""
    A = jax.random.normal(jax.random.key(3), (m, n), jnp.float32)
    key = jax.random.key(42)
    got = fused_gaussian_sketch(A, key, d, interpret=True)
    want = fused_gaussian_ref(A, key, d)
    assert jnp.allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fused_gaussian_statistics():
    G = gaussian_matrix_ref(jax.random.key(7), 512, 2048)
    assert abs(float(G.mean())) < 0.01
    assert abs(float(G.std()) - 1.0) < 0.01
