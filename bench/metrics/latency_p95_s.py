"""95th percentile of the latency of every request of the window, each
timed from when it was due; a rejected, failed or lost request counts as
missing, that is as slower than any answer (host clock)."""
import math

import numpy as np


def read(run):
    reqs = run.records.get("requests")
    if not reqs:
        return None
    lat = np.array([math.inf if q["latency_s"] is None else q["latency_s"]
                    for q in reqs])
    p95 = float(np.percentile(lat, 95, method="higher"))
    if not math.isfinite(p95):
        # More than one request in twenty missing: the tail is the wait.
        return run.records["window_s"] + run.records.get("drain_s", 0.0)
    return p95
