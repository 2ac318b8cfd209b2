"""Device milliseconds per batch of the ops under the program's ``certify``
name scope (the service's blocked certificate of a batch), per chip;
batches are the window's ``serve.dispatch.session`` spans (device trace,
bench/scopes.py)."""
from bench import scopes


def read(run):
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.count("certify"):
        return None
    batches = scopes.session_batches(run.trace)
    return 1e3 * scoped.seconds("certify") / batches if batches else None
