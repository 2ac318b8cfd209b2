"""Mean iterations per solve, from each SolveResult.itn (program counter)."""


def read(run):
    solves = run.records.get("solves")
    if not solves:
        return None
    return sum(s["itn"] for s in solves) / len(solves)
