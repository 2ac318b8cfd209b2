"""Device milliseconds per solve of the ops under the program's ``lsqr``
name scope (the distributed LSQR of ``sketched_lstsq``: its products with
A's rows and their psums), per chip (device trace, bench/scopes.py)."""
from bench import scopes


def read(run):
    solves = run.records.get("solves")
    scoped = scopes.of_run(run)
    if not solves or scoped is None or not scoped.count("lsqr"):
        return None
    return 1e3 * scoped.seconds("lsqr") / len(solves)
