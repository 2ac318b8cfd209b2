"""MicroBatcher release rules + shape-bucket padding exactness (S4).

The padding theorem under test: embedding (A, b) block-diagonally as
A_pad = [[A, 0], [0, I]], b_pad = [b, 0] decouples the padded problem, so
its minimizer is exactly [x*, 0] — also under ridge, and also through a
SKETCHED solve, because every sketch family embeds the padded column
space as well as the original.  The vmapped bucket solves must therefore
match unbatched ``lstsq`` per problem to tight rtol for all six sketch
kinds.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import lstsq
from repro.serve import MicroBatcher, bucket_shape, pad_problem, solve_bucket

SKETCH_KINDS = (
    "gaussian", "uniform_dense", "srht", "clarkson_woodruff",
    "sparse_sign", "uniform_sparse",
)


# ---------------------------------------------------------------- batcher


def test_size_triggered_release():
    mb = MicroBatcher(max_batch=3, max_delay_s=100.0)
    for i in range(7):
        mb.add("k", i, now=0.0)
    out = mb.ready(now=0.0)
    assert [(k, len(v)) for k, v in out] == [("k", 3), ("k", 3)]
    assert mb.pending == 1  # remainder stays queued, too young to release


def test_age_triggered_release():
    mb = MicroBatcher(max_batch=64, max_delay_s=0.010)
    mb.add("k", "a", now=0.0)
    assert mb.ready(now=0.005) == []
    out = mb.ready(now=0.011)
    assert out == [("k", ["a"])]
    assert mb.pending == 0


def test_drain_releases_everything():
    mb = MicroBatcher(max_batch=64, max_delay_s=100.0)
    mb.add("a", 1, now=0.0)
    mb.add("b", 2, now=0.0)
    out = dict(mb.ready(now=0.0, drain=True))
    assert out == {"a": [1], "b": [2]}


def test_keys_do_not_coalesce_across():
    mb = MicroBatcher(max_batch=2, max_delay_s=100.0)
    mb.add("a", 1, now=0.0)
    mb.add("b", 2, now=0.0)
    mb.add("a", 3, now=0.0)
    out = mb.ready(now=0.0)
    assert out == [("a", [1, 3])]


def test_occupancy_accounting():
    mb = MicroBatcher(max_batch=4, max_delay_s=0.0)
    for i in range(6):
        mb.add("k", i, now=0.0)
    mb.ready(now=1.0)
    assert mb.batch_sizes == [4, 2]
    assert mb.mean_occupancy == pytest.approx(6 / 8)


# ------------------------------------------------------------ shape buckets


def test_bucket_shape_geometric():
    assert bucket_shape(60, 7) == (64, 8)
    assert bucket_shape(64, 7) == (128, 8)  # identity rows need the room
    assert bucket_shape(100, 3) == (128, 8)  # min_n floor
    m_pad, n_pad = bucket_shape(1000, 17)
    assert m_pad >= 1000 + (n_pad - 17) and n_pad == 32


def test_bucket_shape_bounds_compile_count():
    shapes = {bucket_shape(m, n) for m in range(40, 200) for n in (3, 5, 9)}
    assert len(shapes) <= 6  # O(log) buckets for 160x3 distinct shapes


def test_pad_problem_structure():
    A = jax.random.normal(jax.random.PRNGKey(0), (10, 3))
    b = jax.random.normal(jax.random.PRNGKey(1), (10,))
    A_pad, b_pad = pad_problem(A, b, 16, 8)
    assert A_pad.shape == (16, 8) and b_pad.shape == (16,)
    assert jnp.array_equal(A_pad[:10, :3], A)
    assert jnp.array_equal(A_pad[10:15, 3:8], jnp.eye(5))
    assert float(jnp.abs(b_pad[10:]).max()) == 0.0


def _stack_padded(problems, m_pad, n_pad):
    pads = [pad_problem(A, b, m_pad, n_pad) for A, b, _ in problems]
    return (
        jnp.stack([p[0] for p in pads]),
        jnp.stack([p[1] for p in pads]),
        jnp.asarray([lam for _, _, lam in problems]),
    )


def _mixed_problems(key, k=4, n=5):
    """k problems of DIFFERENT shapes that share one (m_pad, n_pad) bucket."""
    problems = []
    for i in range(k):
        kA, kb, key = jax.random.split(key, 3)
        m = 40 + 7 * i
        A = jax.random.normal(kA, (m, n))
        b = jax.random.normal(kb, (m,))
        lam = 0.25 if i % 2 else 0.0  # ridge and plain share the bucket
        problems.append((A, b, lam))
    return problems


def test_bucket_direct_matches_unbatched_lstsq():
    problems = _mixed_problems(jax.random.PRNGKey(0))
    m_pad, n_pad = bucket_shape(40 + 7 * 3 , 5)
    A_stack, b_stack, lam = _stack_padded(problems, m_pad, n_pad)
    out = solve_bucket(A_stack, b_stack, lam, certify=True)
    for i, (A, b, l) in enumerate(problems):
        n = A.shape[1]
        x_ref = lstsq(A, b, jax.random.PRNGKey(1), method="direct",
                      reg=l or None).x
        x = out["x"][i, :n]
        assert float(jnp.linalg.norm(x - x_ref)) <= 1e-10 * max(
            1.0, float(jnp.linalg.norm(x_ref))
        )
        # padded coordinates are exactly decoupled -> driven to zero
        assert float(jnp.abs(out["x"][i, n:]).max()) <= 1e-12
        assert float(out["error_bound"][i]) < 1e-10


@pytest.mark.parametrize("kind", SKETCH_KINDS)
def test_padded_vmapped_batch_matches_unbatched(kind):
    """S4: one vmapped sketched batch over the padded stack, per kind.

    ``saa_sas_batch`` problem-batch mode shares ONE S draw and vmaps the
    whole factor+solve over the stack — exactly the bucket execution
    model; every per-problem answer must match its own unbatched direct
    solve.
    """
    from repro.core import saa_sas_batch

    problems = [(A, b, 0.0) for A, b, _ in _mixed_problems(jax.random.PRNGKey(2))]
    m_pad, n_pad = bucket_shape(40 + 7 * 3, 5)
    A_stack, b_stack, _ = _stack_padded(problems, m_pad, n_pad)
    res = saa_sas_batch(
        A_stack, b_stack, jax.random.PRNGKey(3), sketch=kind, iter_lim=80,
    )
    for i, (A, b, _) in enumerate(problems):
        x_ref = lstsq(A, b, jax.random.PRNGKey(4), method="direct").x
        n = A.shape[1]
        rel = float(jnp.linalg.norm(res.x[i, :n] - x_ref)) / max(
            1.0, float(jnp.linalg.norm(x_ref))
        )
        assert rel <= 1e-8, f"{kind}: padded vmapped solve off by {rel:.2e}"
        # padded coordinates decouple and are driven to (numerical) zero
        assert float(jnp.abs(res.x[i, n:]).max()) <= 1e-8


@pytest.mark.parametrize("kind", ["clarkson_woodruff", "uniform_sparse"])
def test_redraw_marks_only_the_lane_the_shared_draw_failed(kind):
    """Under PRNGKey(3) the shared sparse draw hashes two of problem 1's
    padded identity rows into one bucket (its LSQR stops on the condition
    limit, istop 3).  Only that lane is solved again, under a second draw,
    and only it reports ``used_fallback``."""
    from repro.core import saa_sas_batch

    problems = [(A, b, 0.0) for A, b, _ in _mixed_problems(jax.random.PRNGKey(2))]
    m_pad, n_pad = bucket_shape(40 + 7 * 3, 5)
    A_stack, b_stack, _ = _stack_padded(problems, m_pad, n_pad)
    res = saa_sas_batch(
        A_stack, b_stack, jax.random.PRNGKey(3), sketch=kind, iter_lim=80,
    )
    assert [bool(f) for f in res.used_fallback] == [False, True, False, False]
    # the redraw's own stop code is reported, and it converged
    assert int(res.istop[1]) not in (3, 6, 7)


@pytest.mark.parametrize("kind", SKETCH_KINDS)
def test_padded_ridge_solve_matches_unbatched(kind):
    """S4 (ridge): padding exactness survives λ > 0 through the sketched
    path — the √λI tail rides the structured AugmentedSketch, never the
    random block."""
    problems = _mixed_problems(jax.random.PRNGKey(5))
    m_pad, n_pad = bucket_shape(40 + 7 * 3, 5)
    A_stack, b_stack, lam = _stack_padded(problems, m_pad, n_pad)
    for i, (A, b, _) in enumerate(problems):
        reg = float(lam[i]) or None
        x_pad = lstsq(
            A_stack[i], b_stack[i], jax.random.PRNGKey(6), method="saa",
            sketch=kind, reg=reg, iter_lim=80,
        ).x
        x_ref = lstsq(A, b, jax.random.PRNGKey(7), method="direct",
                      reg=reg).x
        n = A.shape[1]
        rel = float(jnp.linalg.norm(x_pad[:n] - x_ref)) / max(
            1.0, float(jnp.linalg.norm(x_ref))
        )
        assert rel <= 1e-8, f"{kind}: padded ridge solve off by {rel:.2e}"
        assert float(jnp.abs(x_pad[n:]).max()) <= 1e-8


def test_solve_bucket_validates_shapes():
    with pytest.raises(ValueError, match="A_stack"):
        solve_bucket(jnp.zeros((2, 8, 4)), jnp.zeros((2, 7)))
