"""Mean SolveResponse.batch_size over the window's answered requests: how
many right-hand sides shared a batch (program counter)."""


def read(run):
    sizes = [q["batch_size"] for q in run.records.get("requests", ())
             if q["batch_size"] is not None]
    return sum(sizes) / len(sizes) if sizes else None
