"""Chip benchmark of the sketch-and-solve least-squares package.

One command runs one cell once (see ``bench/run.py``).  Everything that
belongs to one configuration, traffic mix or metric is a file of its own,
found by the name ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: one deployment (shape, dtype, chips).
- ``bench/traffic/<mix>.json``: one traffic mix, read by the driver it names
  in ``bench/drivers/<driver>.py``.
- ``bench/workloads/<cell>.json``: the limits of the cell's ``correct``.
- ``bench/metrics/<metric>.py``: one reader per metric.
- ``bench/peaks.json``, ``bench/work.py``: the yardstick of the rooflines.
"""
