"""Device milliseconds of all-reduce ops per solve, mean over the chips
(device trace)."""


def _is_allreduce(name: str) -> bool:
    return name.startswith("all-reduce")  # all-reduce.<k>, all-reduce-done


def read(run):
    solves = run.records.get("solves")
    if run.trace is None or not solves or not run.trace.count(_is_allreduce):
        return None
    return 1e3 * run.trace.op_seconds(_is_allreduce) / len(solves)
