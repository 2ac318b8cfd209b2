"""Open-loop Poisson traffic against ``SolveService``.

One tenant's design matrix is prewarmed (its factor built, certified and
the batch-width ladder compiled); then requests arrive at the mix's fixed
rate, each a right-hand side from a pool of separate device arrays made in
set-up, so a submit does no device work.  The arrivals of a window are a
Poisson process conditioned on its count: round(rate x seconds) due times
drawn uniformly from the seed, so every seed sends the same number of
requests in another order.  Each request is timed from when it was due to
when its future resolved; one that is rejected, fails or never resolves
counts as missing.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from bench import problem

EPS32 = 2.0**-23


class Cell:
    def __init__(self, ctx):
        from repro.serve import SolveService, digest_array

        self.ctx = ctx
        cfg, traffic = ctx.config, ctx.traffic
        if cfg["layout"] != "single":
            raise ValueError("service_poisson serves one chip's matrix")
        self.pool = int(traffic["rhs_pool"])
        self.rate = float(traffic["rate_per_s"])
        self.wait_after_s = float(traffic.get("wait_after_close_s", 60.0))
        self.rtol = float(traffic["certified_rtol_x_cond_eps"]) * float(
            cfg["cond"]) * EPS32
        with ctx.timer("generate"):
            self.A, self.bs, self.X_true = problem.generate(
                ctx.key("data"), cfg, self.pool, ctx.devices
            )
        with ctx.timer("digest"):
            digest_array(self.A)  # prewarm's fingerprint finds it memoized
        self.svc = SolveService(
            ctx.key("service"), default_rtol=self.rtol,
            max_batch=int(traffic["max_batch"]),
            max_delay_s=float(traffic["max_delay_s"]),
        )
        with ctx.timer("prewarm"):
            self.svc.prewarm(self.A)
        self.svc.start(poll_s=float(traffic.get("poll_s", 0.0005)))
        with ctx.timer("warm_traffic"):
            self._warm_bursts(int(traffic["max_batch"]))
        self.stats0 = self.svc.stats()

    def _warm_bursts(self, max_batch: int) -> None:
        """Bursts of every width of the batching ladder, so the pump's own
        stacking ops are compiled before the window."""
        import jax.numpy as jnp

        w = 1
        while w <= max_batch:
            jnp.stack([self.bs[j % self.pool] for j in range(w)], axis=1)
            w *= 2
        for size in (1, 2, 3, 5, 9, 17, 33, max_batch):
            futs = [self.svc.submit(self.A, self.bs[j % self.pool],
                                    certified_rtol=self.rtol)
                    for j in range(size)]
            for f in futs:
                f.result(timeout=600)

    def window(self, seconds: float) -> dict:
        from jax.profiler import TraceAnnotation

        rng = self.ctx.rng
        n_req = max(1, int(round(self.rate * seconds)))
        due = np.sort(rng.uniform(0.0, seconds, n_req))
        rhs = rng.integers(0, self.pool, n_req)
        done_at = [None] * n_req
        submit_at = np.zeros(n_req)
        futs = []
        lock = threading.Lock()

        def stamp(i):
            def cb(_f):
                t = time.perf_counter()
                with lock:
                    done_at[i] = t
            return cb

        t0 = time.perf_counter()
        for i in range(n_req):
            lag = t0 + due[i] - time.perf_counter()
            if lag > 0:
                with TraceAnnotation("bench.wait"):
                    time.sleep(lag)
            with TraceAnnotation("bench.submit"):
                submit_at[i] = time.perf_counter() - t0
                f = self.svc.submit(self.A, self.bs[int(rhs[i])],
                                    certified_rtol=self.rtol)
            f.add_done_callback(stamp(i))
            futs.append(f)
        t_close = t0 + seconds
        deadline = max(t_close, time.perf_counter()) + self.wait_after_s
        resps = []
        for f in futs:
            left = deadline - time.perf_counter()
            try:
                resps.append(f.result(timeout=max(left, 0.0)))
            except Exception:  # noqa: BLE001 -- a lost request is missing
                resps.append(None)
        t_stop = time.perf_counter()
        stats = self.svc.stats()
        reqs, answers = [], []
        for i, r in enumerate(resps):
            ok = r is not None and r.ok
            lat = (done_at[i] - t0 - due[i]) if (ok and done_at[i]) else None
            reqs.append({
                "rhs": int(rhs[i]), "ok": ok, "latency_s": lat,
                "batch_size": None if r is None else int(r.batch_size),
                "queued_s": None if r is None else float(r.queued_s),
                "cache_hit": None if r is None else bool(r.cache_hit),
                "path": None if r is None else r.path,
                "answered": r is not None,
                "itn": None if not ok else int(r.result.itn),
            })
            if ok:
                answers.append((int(rhs[i]), np.asarray(r.x, np.float64)))
        self.answers = answers
        late = submit_at - due
        itns = [q["itn"] for q in reqs if q["itn"] is not None]
        hits = [q["cache_hit"] for q in reqs if q["cache_hit"] is not None]
        return {
            "window_s": seconds, "requests": reqs,
            "attempted": n_req,
            "failed": sum(1 for q in reqs if not q["ok"]),
            "unanswered": sum(1 for q in reqs if not q["answered"]),
            "generator_late_p95_s": float(np.percentile(late, 95)),
            "generator_late_max_s": float(late.max()),
            "drain_s": t_stop - t_close,
            "slow_path": stats["slow_path"] - self.stats0["slow_path"],
            "rejected": stats["rejected"] - self.stats0["rejected"],
            "cache_hit_share": sum(hits) / len(hits) if hits else 0.0,
            "itn_mean": sum(itns) / len(itns) if itns else 0.0,
            "batch_mean": float(np.mean([q["batch_size"] for q in reqs
                                         if q["batch_size"] is not None]
                                        or [0])),
        }

    def free_program(self) -> None:
        if self.svc is not None:
            self.svc.stop()
            self.svc = None

    def reference_inputs(self):
        return self.A, self.bs

    def release(self) -> None:
        self.A = self.bs = None


def setup(ctx) -> Cell:
    return Cell(ctx)
