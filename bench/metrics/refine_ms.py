"""Device milliseconds per solve of the ops under the program's ``refine``
name scope (the heavy-ball loop and the residual of the returned iterate),
per chip (device trace, bench/scopes.py)."""
from bench import scopes


def read(run):
    solves = run.records.get("solves")
    scoped = scopes.of_run(run)
    if not solves or scoped is None or not scoped.count("refine"):
        return None
    return 1e3 * scoped.seconds("refine") / len(solves)
