"""Share of the traced window in which the first chip runs no op while the
service's pump is inside a ``serve.dispatch.*`` span, in %: the idle the
host causes with a batch in hand.  The rest of ``device_idle.serve`` is
the offered load's (program span, on the device trace's clock)."""
from bench import scopes


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a, b) -> float:
    """Length of the intersection of two unions of intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    t = run.trace
    if t is None or not t.ops or not run.records.get("requests"):
        return None
    lo, hi = t.window
    dispatch = _union((max(h.start, lo), min(h.end, hi)) for h in t.host
                      if h.name.startswith(scopes.DISPATCH)
                      and h.end > lo and h.start < hi)
    if not dispatch:
        return None
    busy = _union((o.start, o.end) for o in t.ops[min(t.ops)])
    held = sum(e - s for s, e in dispatch)
    return 100.0 * (held - _overlap(dispatch, busy)) * 1e-9 / t.window_s
