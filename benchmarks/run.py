"""Benchmark harness — one module per paper table/figure.

  fig3      paper Fig. 3: runtime vs m, SAA-SAS vs LSQR
  fig4      paper Fig. 4: forward error on the ill-conditioned problem
  sketch    paper §2: operator quality/cost comparison
  kernels   Pallas kernel micro-benches (interpret mode + derived TPU terms)
  dist      distributed sketched LSQ (shard_map) + comm accounting
  stream    streaming engine: tiles/sec + peak-memory proxy vs monolithic
  certified per-method wall time + certified-error columns (BENCH_5.json)
  serve     multi-tenant solve service: closed/open-loop load rows (PR 7)
  cluster   multi-worker pass-1 scaling + kill-and-resume overhead (PR 8)
  obs       tracing-disabled overhead vs a stripped build (PR 9)
  roofline  per-cell roofline terms from the dry-run JSONs

Prints ``name,us_per_call,derived`` CSV.  ``--full`` restores paper-scale
sizes (slow on 1 CPU core).  ``--json [PATH]`` additionally dumps the
``certified`` cell's rows (per-method wall time, forward error vs QR and
the posterior certified-error columns) plus the ``serve`` cell's
throughput/latency rows as machine-readable JSON so the perf/accuracy
trajectory is tracked in git from PR 5 on.  The default path is
``BENCH_{tag}.json`` with ``--tag`` naming the trajectory point (current
PR number; ``--tag ci`` for throwaway CI runs) — committed
``BENCH_N.json`` files are what ``benchmarks/perf_gate.py`` compares
fresh runs against.
"""
import argparse
import json
import sys


def main() -> None:
    import jax

    from .common import use_compile_cache

    jax.config.update("jax_enable_x64", True)
    use_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,fig4,sketch,kernels,dist,stream,"
                         "certified,serve,cluster,obs,roofline")
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--tag", default="9",
                    help="trajectory tag naming the default JSON path "
                         "BENCH_{tag}.json (current PR number, or 'ci')")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write the certified cell's rows as JSON "
                         "(default path: BENCH_{tag}.json; implies the "
                         "certified cell runs)")
    args = ap.parse_args()
    if args.json == "":
        args.json = f"BENCH_{args.tag}.json"
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        # --json implies the trajectory cells (certified + serve +
        # cluster + obs) run: BENCH_{tag}.json must always carry all
        # four row families.
        if (name in ("certified", "serve", "cluster", "obs")
                and args.json is not None):
            return True
        return only is None or name in only

    print("name,us_per_call,derived")
    if want("fig4"):
        from . import error_comparison
        error_comparison.run(m=20000 if args.full else 8192,
                             n=100 if args.full else 64)
    if want("fig3"):
        from . import runtime_comparison
        runtime_comparison.run(full=args.full)
    if want("sketch"):
        from . import sketch_quality
        sketch_quality.run(m=65536 if args.full else 16384)
    if want("kernels"):
        from . import kernels_bench
        kernels_bench.run()
    if want("dist"):
        from . import distributed_bench
        distributed_bench.run()
    if want("stream"):
        from . import streaming_bench
        streaming_bench.run(m=65536 if args.full else 16384)
    rows = []
    if want("certified"):
        from . import certified_bench
        rows += certified_bench.run(m=20000 if args.full else 8192,
                                    n=100 if args.full else 64)
    if want("serve"):
        from . import serve_bench
        rows += serve_bench.run(full=args.full)
    if want("cluster"):
        from . import cluster_bench
        rows += cluster_bench.run(m=65536 if args.full else 16384)
    if want("obs"):
        from . import obs_bench
        rows += obs_bench.run()
    if args.json is not None:
        payload = {
            "bench": "certified_lstsq",
            "schema": 1,
            "rows": rows,
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"wrote {args.json} ({len(rows)} rows)", file=sys.stderr)
    if want("roofline"):
        from . import roofline
        roofline.run()


if __name__ == "__main__":
    main()
