"""Share of its roofline the distributed LSQR reaches, in %.

The least time of the LSQR's own work over the device time of the ops under
the ``lsqr`` name scope (bench/scopes.py).  Its work per solve, per chip,
over its m/chips rows: itn + 1 passes (itn from each SolveResult; one per
iteration and one for the starting residual), each reading the chip's rows
of A and b once and taking A v and A^T u: the count of
``refine_roofline.pass_over_a``, which a loop that forms both products in
one row-blocked pass reaches.  Peaks from bench/peaks.json.
"""
import numpy as np

from bench import scopes, work
from bench.metrics.refine_roofline import pass_over_a


def read(run):
    solves = run.records.get("solves")
    scoped = scopes.of_run(run)
    if not solves or scoped is None:
        return None
    took = scoped.seconds("lsqr")
    if not took:
        return None
    cfg = run.config
    one, _ = work.least_seconds(
        pass_over_a(int(cfg["m"]) // run.chips, int(cfg["n"]),
                    np.dtype(cfg["dtype"]).itemsize),
        work.peaks(run.device_kind))
    return 100.0 * one * sum(s["itn"] + 1 for s in solves) / took
