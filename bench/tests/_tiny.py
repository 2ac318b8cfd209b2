"""Shared tiny-size settings for the CPU tests of the cells."""
import time

# m * n^2 above the service's small-problem cutoff, so requests take the
# session path as they do at the real size.
TINY = {"m": 16384, "n": 128, "sketch_rows": 512}
TINY_SERVE = {"rhs_pool": 8, "rate_per_s": 40.0, "max_batch": 8}


def run(cell, *, seconds=1.0, trace=False, seed=2**32 + 5, **kw):
    from bench import harness

    traffic = TINY_SERVE if "serve" in cell else {}
    return harness.run_cell(
        cell, seed, seconds, trace, t_start=time.perf_counter(),
        require_chip=False, config_override=TINY, traffic_override=traffic,
        compile_cache=False, **kw)
