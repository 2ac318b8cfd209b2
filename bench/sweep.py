#!/usr/bin/env python3
"""Find the highest rate a served cell sustains: one set-up, many rates.

    python3 bench/sweep.py --workload fig3.serve --rates 60,120,180 --seconds 8

First the time of one full batch (``max_batch`` requests submitted at
once, until the last answer); then, at each rate in turn, an open-loop
window of the cell's own traffic with the rate replaced.  Each line gives
the rate offered, the rate answered, p50 and p95 latency from the due time,
the backlog left when the window closed (``drain_s``: how long the last
answers took after it) and the generator's lateness.  The cell's rate is
then fixed by hand at about 0.8 x the highest rate whose p95 stays within
the limit chosen from the batch time, with no growing backlog.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import numpy as np

    from bench import harness, spec as spec_lib

    cell = spec_lib.cell(args.workload)
    harness.use_compile_cache()
    devices = harness.check_devices(cell.chips)
    driver = spec_lib.load_module("drivers", cell.traffic["driver"])
    ctx = harness.Context(cell=args.workload, config=cell.config,
                          traffic=cell.traffic, seed=args.seed,
                          devices=devices, timer=harness.Timer(),
                          rng=np.random.default_rng(args.seed))
    sut = driver.setup(ctx)
    try:
        _sweep(sut, cell, args, ctx)
    finally:
        sut.free_program()
    return 0


def _sweep(sut, cell, args, ctx):
    import numpy as np

    print("setup: " + json.dumps(ctx.timer.parts), flush=True)
    k = int(cell.traffic["max_batch"])
    for _ in range(3):
        t0 = time.perf_counter()
        futs = [sut.svc.submit(sut.A, sut.bs[j % sut.pool],
                               certified_rtol=sut.rtol) for j in range(k)]
        for f in futs:
            f.result(timeout=600)
        print(json.dumps({"full_batch_s": time.perf_counter() - t0,
                          "k": k}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        sut.rate = rate
        rec = sut.window(args.seconds)
        lat = np.array([q["latency_s"] for q in rec["requests"]
                        if q["latency_s"] is not None])
        sizes = [q["batch_size"] for q in rec["requests"]
                 if q["batch_size"] is not None]
        print(json.dumps({
            "rate": rate, "requests": rec["attempted"],
            "failed": rec["failed"],
            "answered_per_s": len(lat) / (args.seconds + rec["drain_s"]),
            "p50_s": float(np.percentile(lat, 50)),
            "p95_s": float(np.percentile(lat, 95)),
            "max_s": float(lat.max()),
            "drain_s": rec["drain_s"],
            "mean_batch": float(np.mean(sizes)),
            "late_p95_s": rec["generator_late_p95_s"],
            "slow_path": rec["slow_path"],
        }), flush=True)


if __name__ == "__main__":
    sys.exit(main())
