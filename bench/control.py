#!/usr/bin/env python3
"""Readings behind a cell's limits: the program's gap and the controls'.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 --seconds 3

For each seed, in one process: the cell's set-up, a short window of its own
traffic at its own size, the plain reference on the same inputs, and the
program's worst gap to it (``max_rel_err``, as a run computes it) with the
run's other checks.  Then the controls, each of which has to come out not
correct:

* the reference one precision step down (``reference.solve(...,
  control=True)``: A and b in bfloat16) in the program's place;
* where the cell's file names a ``program_control`` (the program's own
  lower-precision path, as traffic settings), the program with that path
  switched on, on the same inputs, judged by the run's own checks.

The lower reading of a limit is the largest program gap over the seeds,
the upper the smallest control gap.  The benchmark's own runs never run
this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def readings(name, seeds, seconds, *, require_chip=True, config_override=None,
             traffic_override=None, compile_cache=True):
    """One row per seed: the program's checks, the bf16 reference's gap,
    and, where the cell names one, the program control's checks."""
    import gc

    import numpy as np

    from bench import harness, reference, spec as spec_lib

    cell = spec_lib.cell(name)
    cell.config.update(config_override or {})
    cell.traffic.update(traffic_override or {})
    program_control = cell.limits.get("program_control")
    if compile_cache:
        harness.use_compile_cache()
    devices = harness.check_devices(cell.chips, require_chip=require_chip)
    driver = spec_lib.load_module("drivers", cell.traffic["driver"])

    def window(seed, traffic):
        ctx = harness.Context(cell=name, config=cell.config, traffic=traffic,
                              seed=seed, devices=devices, timer=harness.Timer(),
                              rng=np.random.default_rng(seed))
        sut = driver.setup(ctx)
        records = sut.window(seconds)
        sut.free_program()
        gc.collect()
        return sut, records

    def judged(sut, records, X_ref):
        checks = harness.checks_of(cell, records,
                                   harness.compare(sut.answers, X_ref))
        return {"answers": records["attempted"],
                "checks": {k: c["value"] for k, c in checks.items()},
                "correct": harness.is_correct(checks)}

    out = []
    for seed in seeds:
        sut, records = window(seed, cell.traffic)
        A, bs = sut.reference_inputs()
        X_ref = reference.solve(A, bs, devices)
        X_ctl = reference.solve(A, bs, devices, control=True)
        row = {"seed": seed, "program": judged(sut, records, X_ref),
               "bf16_reference_gap": harness.compare(
                   [(j, X_ctl[:, j]) for j in range(len(bs))], X_ref)}
        sut.release()
        del sut, A, bs
        gc.collect()
        if program_control:
            traffic = {**cell.traffic, **program_control["traffic"]}
            sut, records = window(seed, traffic)
            row["program_control"] = judged(sut, records, X_ref)
            sut.release()
            del sut
            gc.collect()
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = readings(args.workload, seeds, args.seconds)
    summary = {
        "workload": args.workload,
        "lower": max(r["program"]["checks"]["max_rel_err"] for r in rows),
        "upper_bf16_reference": min(r["bf16_reference_gap"] for r in rows),
        "program_correct": all(r["program"]["correct"] for r in rows),
    }
    ctl = [r["program_control"] for r in rows if "program_control" in r]
    if ctl:
        summary["upper_program_control"] = min(
            c["checks"]["max_rel_err"] for c in ctl)
        summary["program_control_correct_any"] = any(c["correct"] for c in ctl)
    summary["seconds_total"] = time.perf_counter() - T_START
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
