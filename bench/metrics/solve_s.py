"""Seconds per solve: the whole closed-loop window over the solves that
completed in it (host clock)."""


def read(run):
    solves = run.records.get("solves")
    if not solves:
        return None
    return run.records["window_s"] / len(solves)
