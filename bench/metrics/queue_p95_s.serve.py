"""95th percentile of SolveResponse.queued_s, submit to dispatch, in
seconds (program counter)."""
import numpy as np


def read(run):
    q = [r["queued_s"] for r in run.records.get("requests", ())
         if r["queued_s"] is not None]
    return float(np.percentile(q, 95)) if q else None
