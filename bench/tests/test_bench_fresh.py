"""fig3.fresh at a tiny size on the CPU, in-process, and its faults."""
import pytest

from bench.tests import _tiny


def test_fresh_solves_run_and_are_correct():
    res = _tiny.run("fig3.fresh")
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_rel_err"]["value"] < (
        res["checks"]["max_rel_err"]["limit"])


def test_fresh_traced_run_reports_per_layer_only():
    res = _tiny.run("fig3.fresh", trace=True)
    assert res["correct"] is True
    # no chip here: only the program's counter has something to read
    assert set(res["metrics"]) == {"iterations"}
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res


def test_an_altered_answer_is_not_correct(monkeypatch):
    import repro.core as core

    real = core.lstsq
    calls = {"n": 0}

    def altered(*args, **kw):
        res = real(*args, **kw)
        calls["n"] += 1
        if calls["n"] == 3:  # the window's first solve (two warm the path)
            res = res._replace(x=res.x.at[0].add(1e-2))
        return res

    monkeypatch.setattr(core, "lstsq", altered)
    res = _tiny.run("fig3.fresh")
    assert calls["n"] >= 3
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > (
        res["checks"]["max_rel_err"]["limit"])


def test_a_solve_stopped_at_its_iteration_limit_is_not_correct(monkeypatch):
    import repro.core as core

    real = core.lstsq
    calls = {"n": 0}

    def stalled(*args, **kw):
        res = real(*args, **kw)
        calls["n"] += 1
        if calls["n"] == 3:  # the window's first solve (two warm the path)
            res = res._replace(istop=res.istop * 0 + 7)
        return res

    monkeypatch.setattr(core, "lstsq", stalled)
    res = _tiny.run("fig3.fresh")
    assert res["failed"] == 1
    assert res["checks"]["failed"] == {"value": 1, "limit": 0}
    assert res["correct"] is False


def test_control_fails_the_limit_and_the_program_passes():
    from bench import control, spec

    limit = spec.cell("fig3.fresh").limits["limits"]["max_rel_err"]
    rows = control.readings("fig3.fresh", [3, 4], 0.5, require_chip=False,
                            config_override=_tiny.TINY, compile_cache=False)
    for row in rows:
        assert row["program"]["correct"] is True
        assert row["program"]["checks"]["max_rel_err"] < limit
        assert row["bf16_reference_gap"] > limit
        assert row["program_control"]["correct"] is False


def test_seed_sets_inputs():
    import jax
    import numpy as np

    from bench import problem

    cfg = {"m": 4096, "n": 32, "cond": 1e4, "beta": 1e-10,
           "dtype": "float32", "layout": "single"}
    devs = jax.devices()[:1]
    big = 2**31 + 17
    a1 = problem.generate(problem.seed_key(big), cfg, 2, devs)[0]
    a2 = problem.generate(problem.seed_key(big), cfg, 2, devs)[0]
    a3 = problem.generate(problem.seed_key(big + 2**32), cfg, 2, devs)[0]
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert not np.array_equal(np.asarray(a1), np.asarray(a3))
    with pytest.raises(ValueError):
        problem.seed_key(-1)
