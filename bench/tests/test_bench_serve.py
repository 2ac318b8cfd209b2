"""fig3.serve at a tiny size on the CPU, in-process, and its faults."""
import numpy as np

from bench.tests import _tiny


def test_served_requests_run_and_are_correct():
    res = _tiny.run("fig3.serve")
    assert res["correct"] is True
    assert res["attempted"] == 40 and res["failed"] == 0
    assert set(res["metrics"]) == {"latency_p95_s", "setup_s"}
    assert res["checks"]["failed"] == {"value": 0, "limit": 0}


def test_served_traced_run_reads_the_service_counters_and_spans():
    res = _tiny.run("fig3.serve", trace=True)
    assert res["correct"] is True
    got = res["metrics"]
    assert {"rhs_per_batch.serve", "queue_p95_s.serve",
            "certify_ms.serve"} <= set(got)
    assert got["rhs_per_batch.serve"]["value"] >= 1
    assert got["certify_ms.serve"]["value"] > 0


def test_an_altered_answer_is_not_correct(monkeypatch):
    from repro.serve.service import SolveService

    real = SolveService._resolve
    seen = {"n": 0}

    def altered(self, r, res, cert, path, hit, batch):
        seen["n"] += 1
        if seen["n"] == 100:  # a request of the window, after the warm-up
            x = np.array(res.x)
            x[0] += 1e-1
            res = res._replace(x=x)
        return real(self, r, res, cert, path, hit, batch)

    monkeypatch.setattr(SolveService, "_resolve", altered)
    res = _tiny.run("fig3.serve")
    assert seen["n"] >= 100
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > (
        res["checks"]["max_rel_err"]["limit"])


def test_a_refused_request_is_not_correct(monkeypatch):
    from repro.serve.service import SolveResponse, SolveService

    real = SolveService._resolve
    seen = {"n": 0}

    def refused(self, r, res, cert, path, hit, batch):
        seen["n"] += 1
        if seen["n"] != 100:  # a request of the window, after the warm-up
            return real(self, r, res, cert, path, hit, batch)
        r.future.set_result(SolveResponse(
            status="rejected", x=None, result=None, certificate=None,
            reason="planted refusal", path=path, cache_hit=hit,
            batch_size=batch, queued_s=0.0, latency_s=0.0))

    monkeypatch.setattr(SolveService, "_resolve", refused)
    res = _tiny.run("fig3.serve")
    assert seen["n"] >= 100
    assert res["failed"] == 1
    assert res["checks"]["failed"] == {"value": 1, "limit": 0}
    assert res["checks"]["max_rel_err"]["value"] < (
        res["checks"]["max_rel_err"]["limit"])
    assert res["correct"] is False
