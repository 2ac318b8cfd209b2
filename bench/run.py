#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 -m bench.run ...            # the same, from the checkout's root

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and ``checks`` last); the last lines of standard error are
the numbers compared, each beside its limit.  Without an accelerator, with
fewer chips than the cell needs, or outside a checkout of the repository,
it exits non-zero and prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench/run.py: no src/repro under {ROOT}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # libtpu's own log files would go to a fixed /tmp path.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness

    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START,
        )
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
