"""Share of the traced window in which no op ran on the chip, in %, mean
over the chips (device trace)."""


def read(run):
    if run.trace is None or not run.trace.ops or not run.records.get("solves"):
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s() / run.trace.window_s)
