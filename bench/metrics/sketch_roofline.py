"""Share of its roofline the sketch apply reaches, in %.

The least time of the CountSketch's own work per solve (bench/work.py:
[A b] read once, buckets and signs read once, S[A b] written once, one add
per entry; per chip, over its m/chips rows) at the chip's peaks
(bench/peaks.json), over the device time of the kernel's ops per solve.
"""
from bench import work
from bench.metrics import _sketch


def read(run):
    s = _sketch.per_solve_seconds(run)
    if not s:
        return None
    cfg = run.config
    m_chip = int(cfg["m"]) // run.chips
    need = work.countsketch_apply(m_chip, int(cfg["n"]) + 1,
                                  int(cfg["sketch_rows"]))
    least, _ = work.least_seconds(need, work.peaks(run.device_kind))
    return 100.0 * least / s
