"""bench/work.py: operator work from shapes, and the peak table."""
import pytest

from bench import work


def test_countsketch_work_at_fig3_shape():
    # [A b] at m = 2^20, n = 1000, d = 4000, f32: A and b read once, the
    # int32 bucket row and the f32 sign row read once, S[A b] written once.
    m, cols, d = 1 << 20, 1001, 4000
    w = work.countsketch_apply(m, cols, d)
    assert w["flops"] == m * cols
    assert w["bytes"] == 4 * m * cols + 4 * m + 4 * m + 4 * d * cols
    assert w["bytes"] == 4_222_902_912


def test_least_time_is_hbm_bound_for_the_sketch():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_seconds(work.countsketch_apply(1 << 20, 1001, 4000),
                                  peak)
    assert bound == "hbm"
    assert t == pytest.approx(4_222_902_912 / 819e9)
    assert 5.1e-3 < t < 5.2e-3


def test_ops_bound_when_operations_dominate():
    t, bound = work.least_seconds({"flops": 197e12, "bytes": 1.0},
                                  work.peaks("TPU v5 lite"))
    assert bound == "ops" and t == pytest.approx(1.0)


def test_work_ignores_kernel_tiling():
    # The same operator work whatever blocks a kernel would use: the counts
    # take shapes only.
    import inspect

    assert list(inspect.signature(work.countsketch_apply).parameters) == [
        "m", "cols", "d", "itemsize"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("cpu")
