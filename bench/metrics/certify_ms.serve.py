"""Milliseconds per batch of the program's ``serve.certify`` spans
(repro.obs, which blocks on the device while it traces)."""


def read(run):
    durs = [e["dur"] for e in run.spans or ()
            if e.get("name") == "serve.certify" and e.get("ph") == "X"]
    return sum(durs) / len(durs) / 1e3 if durs else None
