"""Device milliseconds of the CountSketch kernel per solve, per chip
(device trace)."""
from bench.metrics import _sketch


def read(run):
    s = _sketch.per_solve_seconds(run)
    return None if s is None else s * 1e3
