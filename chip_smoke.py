#!/usr/bin/env python3
"""Chip smoke test: the library's main paths on a TPU, at the paper's width.

    python chip_smoke.py               # one chip: phases 1-3
    python chip_smoke.py --chips 4     # four chips: phase 4 only

Phases (one chip; f32 working precision, bf16 only for precision="mixed"):

1. ``lstsq(A, b, key)`` with every default (method "auto" -> iterative
   sketching, CountSketch, backend "auto" -> compiled Pallas) at the
   paper's Fig. 3 width, m = 2^20, n = 1000.  Checked against the
   generator's ``x_true`` and against Householder QR (``core/direct.py``)
   on the same data.
2. Every kernel-backed sketch kind (gaussian, uniform_dense, srht,
   clarkson_woodruff) through ``lstsq(..., backend="pallas")`` beside
   ``backend="reference"`` on the same device and problem, each at full
   and at mixed precision, at m = 2^17, n = 1000 (the dense kinds hold a
   d x m sketch matrix, 16.8 GB at m = 2^20), cond = 1e2: a bf16 sketch
   preconditions only problems with cond * eps(bf16) well below 1.
3. ``SolveService``: prewarmed, two tenants submit 16 requests against two
   2^16 x 256 matrices; every response must be ok and match QR.

Four chips: 4. ``sketched_lstsq`` with A (m = 2^22, n = 1000, f32; 16.8 GB,
more than one chip holds) generated already row-sharded over a 4-chip
mesh.  Checked against ``x_true``; every chip must hold its quarter of A,
and the compiled solve must move no shard of A between chips and fit
each chip's memory.

Answers are held to two tolerances (relative 2-norm error), both from
f32's machine epsilon eps = 2^-23 and the problem's cond:

- ``tol_direct(cond) = cond * eps``: the first-order forward error of a
  backward-stable f32 solve.  Householder QR, the direct reference, is
  held to it, and so is every comparison with QR.
- ``tol_sketched(cond) = max(cond * eps / 10, 100 * eps)``: the sketched
  solvers refine against residuals computed at working precision and land
  below QR's error (on the chip, 8.6e-6 at cond 1e4 and under 7e-7 at
  cond 1e2), so they are held to a tenth of it, never below 100 ulps.
- ``SolveService`` answers are held to the SLO they were certified at,
  ``10 * tol_direct(cond)`` (the service stops refining once it is met).

A contraction that slips to one bf16 pass (relative error ~2^-9) shows in
one of two ways: in the residuals, as errors orders of magnitude above
these tolerances; in the sketch alone, as a preconditioner too weak to
converge.  So every solve also fails on LSQR's condition-limit or
iteration-limit stop (istop 3, 6, 7), and a Pallas full-precision solve
fails when it takes more than ``2 * r + 2`` iterations where the
reference path took r (a bf16-contracted sketch took 15-16 against 3-5).

Inputs come from ``generate_problem`` with ``--seed``.  The script exits
non-zero, without a result line, when JAX finds no TPU or any phase fails.
Its last line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

EPS_F32 = 2.0**-23
# LSQR stops (SciPy's codes) that mean the solve did not converge: the
# condition-number limit (3, 6) and the iteration limit (7).
STALLED = {3: "condition limit", 6: "condition limit at eps", 7: "iteration limit"}


def tol_direct(cond: float) -> float:
    """Forward-error bound of a backward-stable f32 solve (QR)."""
    return cond * EPS_F32


def tol_sketched(cond: float) -> float:
    """Forward-error bound the sketched solvers are held to."""
    return max(cond * EPS_F32 / 10, 100 * EPS_F32)


def iteration_limit(reference_itn: int) -> int:
    """Most iterations a Pallas full-precision solve may take."""
    return 2 * reference_itn + 2


def _import_repo() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(
            "chip_smoke.py: src/repro not found next to this script; run it "
            "from a checkout of the repository"
        )
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def log(msg: str) -> None:
    print(msg, flush=True)


def _rel(x, ref) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref))


def _check(failures: list, name: str, err: float, tol: float) -> None:
    ok = err <= tol
    log(f"  {name}: rel err {err:.3e} (tol {tol:.3e}) {'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append(f"{name}: {err:.3e} > {tol:.3e}")


def _check_stop(failures: list, name: str, istop: int, itn: int) -> None:
    if istop in STALLED:
        log(f"  {name}: stopped on the {STALLED[istop]} (istop {istop}) "
            f"after {itn} iterations FAIL")
        failures.append(f"{name}: istop {istop} ({STALLED[istop]}), {itn} "
                        "iterations")


def _problem(seed: int, m: int, n: int, cond: float):
    import jax
    import jax.numpy as jnp

    from repro.core import generate_problem

    prob = generate_problem(
        jax.random.key(seed), m, n, cond=cond, dtype=jnp.float32,
        method="fast",
    )
    jax.block_until_ready(prob.A)
    return prob


def _direct(A, b):
    from repro.core import qr_solve

    return qr_solve(A, b).block_until_ready()


def phase_main(m: int, n: int, cond: float, seed: int, *, compiled: bool) -> list:
    """Phase 1: ``lstsq`` with defaults; returns the failures."""
    import jax

    from repro.core import lstsq, resolve_backend

    failures: list = []
    rb = resolve_backend("auto")
    log(f"phase 1: lstsq defaults, m={m} n={n} f32 cond={cond:g}; "
        f"backend auto -> {rb.name} interpret={rb.interpret}")
    if compiled and (rb.name != "pallas" or rb.interpret):
        failures.append(f"backend auto resolved to {rb}, not compiled Pallas")
    t0 = time.perf_counter()
    prob = _problem(seed, m, n, cond)
    log(f"  generate: {time.perf_counter() - t0:.3f} s")
    key = jax.random.key(seed + 1)
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        res = lstsq(prob.A, prob.b, key)
        x = res.x.block_until_ready()
        log(f"  lstsq {run}: {time.perf_counter() - t0:.3f} s, method "
            f"{res.method}, {int(res.itn)} iterations, istop {int(res.istop)}")
    if res.method != "iterative":
        failures.append(f"method auto selected {res.method}, not iterative")
    _check_stop(failures, "lstsq", int(res.istop), int(res.itn))
    _check(failures, "lstsq vs x_true", _rel(x, prob.x_true), tol_sketched(cond))
    t0 = time.perf_counter()
    x_qr = _direct(prob.A, prob.b)
    log(f"  qr_solve: {time.perf_counter() - t0:.3f} s")
    _check(failures, "qr_solve vs x_true", _rel(x_qr, prob.x_true),
           tol_direct(cond))
    _check(failures, "lstsq vs qr_solve", _rel(x, x_qr), tol_direct(cond))
    return failures


KINDS = ("gaussian", "uniform_dense", "srht", "clarkson_woodruff")
# Kinds whose solves run side by side in phase 2.  Each solve compiles its
# own program (a 4000 x 1000 QR alone takes ~40 s to compile for the
# chip), so the solves of a group compile concurrently; the dense kinds
# hold a 2.1 GB d x m sketch per solve, so they get groups of their own.
GROUPS = (("srht", "clarkson_woodruff"), ("gaussian",), ("uniform_dense",))
RUNS = (("reference", "full"), ("pallas", "full"), ("pallas", "mixed"))


def phase_kinds(m: int, n: int, cond: float, seed: int, *, compiled: bool) -> list:
    """Phase 2: each kernel-backed kind, Pallas beside the reference path.

    Per kind: the reference path at full precision, Pallas at full and at
    mixed precision, all on one problem.  ``cond`` must suit mixed
    precision: a bf16-rounded sketch only preconditions problems with
    cond * eps(bf16) well below 1 (the certified tier escalates the
    others to full precision).
    """
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from repro.core import lstsq, resolve_backend

    failures: list = []
    rb = resolve_backend("pallas")
    log(f"phase 2: sketch kinds, m={m} n={n} f32 cond={cond:g}; pallas "
        f"interpret={rb.interpret}")
    if compiled and rb.interpret:
        failures.append("backend pallas resolved to interpret mode")
    prob = _problem(seed + 2, m, n, cond)
    key = jax.random.key(seed + 3)
    tol = tol_sketched(cond)

    def solve(kind, backend, precision):
        t0 = time.perf_counter()
        res = lstsq(prob.A, prob.b, key, sketch=kind, backend=backend,
                    precision=precision)
        x = res.x.block_until_ready()
        return (x, time.perf_counter() - t0, int(res.itn), int(res.istop),
                res.method)

    for group in GROUPS:
        runs = [(k, b, p) for k in group for b, p in RUNS]
        with ThreadPoolExecutor(max_workers=len(runs)) as pool:
            futs = [pool.submit(solve, *r) for r in runs]
            out = dict(zip(runs, (f.result() for f in futs)))
        for (kind, backend, precision), (_, secs, itn, istop, meth) in out.items():
            log(f"  {kind} {backend} {precision}: {secs:.3f} s in a "
                f"concurrent group (compile included), {meth}, {itn} "
                f"iterations, istop {istop}")
            _check_stop(failures, f"{kind} {backend} {precision}", istop, itn)
        for kind in group:
            x_ref, _, ref_itn = out[kind, "reference", "full"][:3]
            _check(failures, f"{kind} reference vs x_true",
                   _rel(x_ref, prob.x_true), tol)
            for backend, precision in RUNS[1:]:
                _check(failures, f"{kind} {backend} {precision} vs reference",
                       _rel(out[kind, backend, precision][0], x_ref), tol)
            itn = out[kind, "pallas", "full"][2]
            if itn > iteration_limit(ref_itn):
                log(f"  {kind} pallas full: {itn} iterations against the "
                    f"reference's {ref_itn} FAIL")
                failures.append(
                    f"{kind} pallas full: {itn} iterations > "
                    f"{iteration_limit(ref_itn)} (reference {ref_itn}): "
                    "the sketch is not at full precision"
                )
    return failures


def phase_serve(m: int, n: int, cond: float, seed: int, *, requests: int) -> list:
    """Phase 3: ``SolveService``, two tenants, ``requests`` requests."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.common import matmul
    from repro.serve import SolveService

    failures: list = []
    # An SLO an f32 certificate can meet: the certified bound overestimates
    # the error, so the SLO is ten times QR's own bound.
    rtol = 10 * tol_direct(cond)
    log(f"phase 3: SolveService, two tenants x {requests // 2} requests, "
        f"{m}x{n} f32 cond={cond:g}, certified_rtol {rtol:.3e}")
    tenants = {}
    for i, name in enumerate(("alice", "bob")):
        prob = _problem(seed + 10 + i, m, n, cond)
        X = jax.random.normal(
            jax.random.key(seed + 20 + i), (n, requests // 2), jnp.float32
        )
        tenants[name] = (prob.A, matmul(prob.A, X), X)
    svc = SolveService(jax.random.key(seed + 30), default_rtol=rtol,
                       max_batch=requests // 2)
    t0 = time.perf_counter()
    for A, _, _ in tenants.values():
        svc.prewarm(A)
    log(f"  prewarm: {time.perf_counter() - t0:.3f} s")
    svc.start()
    try:
        t0 = time.perf_counter()
        futs = [
            (name, j, svc.submit(A, B[:, j], certified_rtol=rtol, tenant=name))
            for name, (A, B, _) in tenants.items()
            for j in range(B.shape[1])
        ]
        resps = [(name, j, f.result(timeout=600)) for name, j, f in futs]
        log(f"  {len(resps)} requests answered in "
            f"{time.perf_counter() - t0:.3f} s")
    finally:
        svc.stop()
    for name, j, r in resps:
        if not r.ok:
            failures.append(f"{name}[{j}] rejected: {r.reason}")
            log(f"  {name}[{j}]: rejected ({r.reason})")
            continue
        A, B, X = tenants[name]
        label = f"{name}[{j}] ({r.path}, {int(r.result.itn)} iterations)"
        _check_stop(failures, label, int(r.result.istop), int(r.result.itn))
        if not bool(r.certificate.passed):
            failures.append(f"{label}: answered with a failed certificate")
        # Held to the SLO it certified; QR's own error adds to the gap to QR.
        _check(failures, f"{label} vs x_true", _rel(r.x, X[:, j]), rtol)
        _check(failures, f"{label} vs qr_solve", _rel(r.x, _direct(A, B[:, j])),
               rtol + tol_direct(cond))
    return failures


# Collective ops in optimized HLO, with the shapes of their results.
_COLLECTIVE = re.compile(
    r"= (.+?) (all-gather|all-to-all|collective-permute|all-reduce"
    r"|reduce-scatter)(?:-start)?\("
)
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def check_sharded_program(compiled, m: int, n: int, chips: int,
                          hbm_bytes: float | None = None) -> list:
    """What the compiled row-sharded solve may not do; returns the failures.

    No collective may move as much as one chip's shard of A (m/chips x n):
    the solve communicates only the sketch and LSQR's vectors.  Each chip's
    arguments hold its shard of A and nothing of A besides, and with the
    program's temporaries they fit ``hbm_bytes`` when it is given.
    """
    failures = []
    shard = (m // chips) * n
    moved = {}
    for types, op in _COLLECTIVE.findall(compiled.as_text()):
        size = max(
            (math.prod(int(d) for d in dims.split(",") if d)
             for dims in _SHAPE.findall(types)),
            default=0,
        )
        moved[op] = max(moved.get(op, 0), size)
    log(f"  compiled collectives, largest result (elements): {moved}; "
        f"shard of A {shard}")
    for op, size in moved.items():
        if size >= shard:
            failures.append(f"{op} of {size} elements moves a shard of A")
    mem = compiled.memory_analysis()
    args, temp = mem.argument_size_in_bytes, mem.temp_size_in_bytes
    shard_bytes = 4 * shard
    log(f"  compiled per-chip memory: arguments {args} B, temporaries "
        f"{temp} B (shard of A {shard_bytes} B)")
    if args >= 2 * shard_bytes:
        failures.append(f"arguments of {args} B per chip hold more than a "
                        f"shard of A ({shard_bytes} B)")
    if hbm_bytes is not None and args + temp >= hbm_bytes:
        failures.append(f"{args + temp} B per chip exceed {hbm_bytes:g} B")
    return failures


def phase_sharded(m: int, n: int, cond: float, seed: int, *, chips: int) -> list:
    """Phase 4: ``sketched_lstsq`` on A row-sharded over ``chips`` devices."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import Problem, generate_problem, sketched_lstsq
    from repro.sharding import make_mesh

    failures: list = []
    devices = jax.devices()[:chips]
    mesh = make_mesh((chips,), ("data",), devices=devices)
    log(f"phase 4: sketched_lstsq, m={m} n={n} f32 cond={cond:g}, A "
        f"row-sharded over {chips} devices")
    rows, rep = NamedSharding(mesh, P("data", None)), NamedSharding(mesh, P())
    vec = NamedSharding(mesh, P("data"))
    gen = jax.jit(
        lambda k: generate_problem(
            k, m, n, cond=cond, dtype=jnp.float32, method="fast"
        ),
        out_shardings=Problem(
            A=rows, b=vec, x_true=rep, r_true=vec, cond=rep, beta=rep
        ),
    )
    t0 = time.perf_counter()
    prob = gen(jax.random.key(seed + 40))
    jax.block_until_ready(prob.A)
    log(f"  generate (sharded): {time.perf_counter() - t0:.3f} s")
    shards = {s.device: s.data.shape for s in prob.A.addressable_shards}
    log(f"  A shards: {[(d.id, shp) for d, shp in shards.items()]}")
    if set(shards) != set(devices) or any(
        shp != (m // chips, n) for shp in shards.values()
    ):
        failures.append(f"A is not spread in quarters: {shards}")
    key = jax.random.key(seed + 41)
    # The program the solve compiles to, as a caller's jit would see it.
    t0 = time.perf_counter()
    compiled = (
        jax.jit(partial(sketched_lstsq, mesh=mesh))
        .lower(prob.A, prob.b, key)
        .compile()
    )
    log(f"  compile (for inspection): {time.perf_counter() - t0:.3f} s")
    hbm = devices[0].memory_stats() or {}
    failures += check_sharded_program(compiled, m, n, chips,
                                      hbm.get("bytes_limit"))
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        res = sketched_lstsq(prob.A, prob.b, key, mesh=mesh)
        x = res.x.block_until_ready()
        log(f"  sketched_lstsq {run}: {time.perf_counter() - t0:.3f} s, "
            f"{int(res.itn)} iterations, istop {int(res.istop)}")
    _check_stop(failures, "sketched_lstsq", int(res.istop), int(res.itn))
    _check(failures, "sketched_lstsq vs x_true",
           _rel(x, prob.x_true), tol_sketched(cond))
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_repo()
    import jax

    from benchmarks.common import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU attached (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
        f"compile cache {use_compile_cache()}")

    if args.chips == 4:
        phases = [lambda: phase_sharded(1 << 22, 1000, 1e4, args.seed, chips=4)]
    else:
        phases = [
            lambda: phase_main(1 << 20, 1000, 1e4, args.seed, compiled=True),
            lambda: phase_kinds(1 << 17, 1000, 1e2, args.seed, compiled=True),
            lambda: phase_serve(1 << 16, 256, 1e3, args.seed, requests=16),
        ]
    failures = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            failures += phase()
        except Exception as e:  # a phase that raises fails the smoke test
            import traceback

            traceback.print_exc()
            failures.append(f"{type(e).__name__}: {e}")
        log(f"  phase time: {time.perf_counter() - t0:.3f} s")
    if failures:
        for f in failures:
            print(f"FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
