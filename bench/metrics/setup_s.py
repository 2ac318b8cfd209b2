"""Process start to the first timed request, in seconds (host clock)."""


def read(run):
    return run.setup_s
