"""Share of its roofline the refinement reaches, in %.

The least time of the refinement's own work over the device time of the ops
under the ``refine`` name scope (bench/scopes.py).  Its work per solve, per
chip, over its m/chips rows: itn + 1 passes over A (itn from each
SolveResult), one per iteration and one for the residual of the returned
iterate, each reading A and b once and taking r = b - A x and A^T r.  That
is what any implementation needs, one that fuses the two products of an
iteration into one pass included.  Peaks from bench/peaks.json.
"""
import numpy as np

from bench import scopes, work


def pass_over_a(m: int, n: int, itemsize: int) -> dict:
    """One pass: A (m x n) and b (m) read once, 4 m n operations."""
    return {"flops": 4 * m * n, "bytes": (m * n + m) * itemsize}


def read(run):
    solves = run.records.get("solves")
    scoped = scopes.of_run(run)
    if not solves or scoped is None:
        return None
    took = scoped.seconds("refine")
    if not took:
        return None
    cfg = run.config
    one, _ = work.least_seconds(
        pass_over_a(int(cfg["m"]) // run.chips, int(cfg["n"]),
                    np.dtype(cfg["dtype"]).itemsize),
        work.peaks(run.device_kind))
    return 100.0 * one * sum(s["itn"] + 1 for s in solves) / took
