"""Closed loop of solves: one caller, a fresh PRNG key per call.

Each call is the configuration's entry point, as users call it:
``lstsq(A, b, key)`` on one chip, or ``sketched_lstsq(A, b, key, mesh=...)``
with A row-sharded over the chips.  The right-hand sides cycle through a
pool made in set-up; the order of the pool and the keys come from the seed.
Every answer is kept for the comparison after the window; a solve that
stopped on a condition or iteration limit (LSQR's istop 3, 6, 7) returned
no answer of its stated accuracy and counts as failed.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import problem

STALLED = (3, 6, 7)


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg, traffic = ctx.config, ctx.traffic
        self.pool = int(traffic["rhs_pool"])
        self.kwargs = dict(traffic.get("solver_kwargs", {}))
        with ctx.timer("generate"):
            self.A, self.bs, self.X_true = problem.generate(
                ctx.key("data"), cfg, self.pool, ctx.devices
            )
        self.base_key = ctx.key("solve")
        entry = cfg["entry"]
        if entry == "lstsq":
            self.call = self._lstsq
        elif entry == "sketched_lstsq":
            self.mesh = problem.row_mesh(cfg, ctx.devices)
            self.kwargs.setdefault("axes", (cfg["mesh_axis"],))
            self.call = self._sketched
        else:
            raise ValueError(f"unknown entry {entry!r}")
        # The pool's order: each pass a fresh permutation drawn from the seed.
        self.order = np.concatenate(
            [ctx.rng.permutation(self.pool) for _ in range(4096 // self.pool + 1)]
        )
        self.records: list = []
        with ctx.timer("compile_load"):
            warm = jax.random.fold_in(self.base_key, 0xFFFFFFFF)
            for _ in range(2):
                jax.block_until_ready(self.call(self.bs[0], warm).x)

    def _lstsq(self, b, key):
        import repro.core as core

        return core.lstsq(self.A, b, key, **self.kwargs)

    def _sketched(self, b, key):
        import repro.core as core

        return core.sketched_lstsq(self.A, b, key, mesh=self.mesh,
                                   **self.kwargs)

    def window(self, seconds: float) -> dict:
        """Solve back to back until ``seconds`` have passed; the window ends
        when the last solve started in it has returned."""
        from jax.profiler import TraceAnnotation

        recs = []
        t_start = time.perf_counter()
        t_end = t_start + seconds
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t_end and i > 0:
                break
            j = int(self.order[i % len(self.order)])
            with TraceAnnotation("bench.solve"):
                key = jax.random.fold_in(self.base_key, i)
                res = self.call(self.bs[j], key)
                res.x.block_until_ready()
            recs.append((j, res))
            i += 1
        t_stop = time.perf_counter()
        self.records = recs
        host = jax.device_get([(r.x, r.itn, r.istop) for _, r in recs])
        solves = [
            {"rhs": j, "itn": int(itn), "istop": int(istop)}
            for (j, _), (_, itn, istop) in zip(recs, host)
        ]
        self.answers = [(j, np.asarray(x, np.float64))
                        for (j, _), (x, _, _) in zip(recs, host)]
        return {"window_s": t_stop - t_start, "solves": solves,
                "attempted": len(recs),
                "failed": sum(s["istop"] in STALLED for s in solves),
                "itn_mean": sum(s["itn"] for s in solves) / len(solves)}

    def free_program(self) -> None:
        self.records = []

    def reference_inputs(self):
        return self.A, self.bs

    def release(self) -> None:
        self.A = self.bs = None


def setup(ctx) -> Cell:
    return Cell(ctx)
