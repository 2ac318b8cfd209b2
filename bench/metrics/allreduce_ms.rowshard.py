"""Device milliseconds of all-reduce ops per solve, mean over the chips
(device trace).

On a v5e an all-reduce that XLA keeps as the program wrote it is named
after the JAX primitive, ``psum.<k>`` (LSQR's psums of an n-vector and of
scalars), and one that XLA made by combining several ``all-reduce.<k>``
(the psum of the sketch S[A b]); both have the opcode ``all-reduce``.
``allreduce_ms`` matches only the second name, so it misses LSQR's.
"""
import re

ALL_REDUCE = re.compile(r"^(all-reduce|psum)(-start|-done)?(\.\d+)?$")


def is_allreduce(name: str) -> bool:
    return bool(ALL_REDUCE.match(name))


def read(run):
    solves = run.records.get("solves")
    if run.trace is None or not solves or not run.trace.count(is_allreduce):
        return None
    return 1e3 * run.trace.op_seconds(is_allreduce) / len(solves)
