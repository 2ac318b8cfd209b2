"""One run of one cell: set-up, the measured window, the check, the line.

The order is fixed:

1. set-up, timed from process start and split into named parts: JAX's
   import and device check, generating the inputs on the device, loading or
   compiling the programs, and whatever else the driver warms;
2. the window of ``--seconds`` (under the profiler with ``--trace 1``),
   with the compilations that start inside it counted;
3. the chips' peak memory, then the program's state freed;
4. the plain reference (``bench/reference.py``) on the same inputs, and
   every answer of the window compared with it;
5. the metrics: the cell's end-to-end ones with ``--trace 0``, its
   per-layer ones with ``--trace 1``; each from its reader.

The last lines on standard error are the numbers compared, each beside its
limit; the last line on standard output is the result.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import spec as spec_lib

CACHE_DIR = spec_lib.ROOT / ".jax_cache"
OUT_DIR = spec_lib.ROOT / ".bench_out"


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device with no peak table."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Timer:
    """Named parts of the set-up, in seconds."""

    def __init__(self):
        self.parts: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.parts[name] = self.parts.get(name, 0.0) + (
                time.perf_counter() - t0)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's files, its chips, and the seed."""

    cell: str
    config: dict
    traffic: dict
    seed: int
    devices: list
    timer: Timer
    rng: np.random.Generator

    def key(self, purpose: str):
        """A PRNG key of the seed for one purpose (data, solve, ...)."""
        import jax
        import zlib

        from bench.problem import seed_key

        return jax.random.fold_in(seed_key(self.seed),
                                  zlib.crc32(purpose.encode()) & 0x7FFFFFFF)


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    cell: str
    config: dict
    traffic: dict
    chips: int
    device_kind: str
    setup_s: float
    records: dict  # the driver's window records
    trace: object | None = None  # bench.trace.Trace in a traced run
    spans: list | None = None  # the program's repro.obs events, traced run


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    ``.jax_cache/`` of the checkout; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int, *, require_chip: bool = True):
    """The first ``chips`` devices; raises :class:`NoChip` off an
    accelerator, with too few chips, or for a device with no peaks."""
    import jax

    from bench import work

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX platform is "
                         f"{devices[0].platform!r}")
        try:
            work.peaks(devices[0].device_kind)
        except KeyError as e:
            raise NoChip(str(e)) from None
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts programs lowered while ``active`` (persistent-cache hits
    included: a lowering inside the window is work the window should not
    do)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def compare(answers, X_ref) -> float:
    """Worst relative 2-norm gap of the answers to the reference's x_j."""
    worst = 0.0
    ref_norm = np.linalg.norm(X_ref, axis=0)
    for j, x in answers:
        gap = float(np.linalg.norm(x - X_ref[:, j]) / ref_norm[j])
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def checks_of(cell: spec_lib.Cell, records: dict, gap: float) -> dict:
    """Each number compared, beside its limit (from the cell's file).

    ``failed`` counts the window's requests or solves that the driver
    found failed: refused, lost, or stopped at a solver's limit."""
    lim = cell.limits["limits"]
    out = {"max_rel_err": {"value": gap, "limit": lim["max_rel_err"]},
           "failed": {"value": records["failed"],
                      "limit": lim.get("failed", 0)}}
    if not records.get("attempted"):
        out["answered"] = {"value": 0, "limit": 1}
    return out


def is_correct(checks: dict) -> bool:
    ok = True
    for name, c in checks.items():
        if name == "answered":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= math.isfinite(c["value"]) and c["value"] <= c["limit"]
    return bool(ok)


def read_metrics(entries: list, run: Run, bench_dir=spec_lib.BENCH) -> dict:
    out = {}
    for m in entries:
        reader = spec_lib.load_module("metrics", m["name"], bench_dir)
        value = reader.read(run)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             config_override: dict | None = None,
             traffic_override: dict | None = None,
             compile_cache: bool = True, root=spec_lib.ROOT,
             bench_dir=spec_lib.BENCH) -> dict:
    """One run of cell ``name``; returns the result line as a dict."""
    cell = spec_lib.cell(name, root, bench_dir)
    cell.config.update(config_override or {})
    cell.traffic.update(traffic_override or {})
    timer = Timer()
    cache = use_compile_cache() if compile_cache else "off"
    devices = check_devices(cell.chips, require_chip=require_chip)
    counter = CompileCounter()
    # From process start: the interpreter, JAX's import and the chips' init.
    timer.parts["import_init"] = time.perf_counter() - t_start
    dev = devices[0]
    log(f"cell {name}: seed {seed}, {seconds:g} s, trace {int(trace)}; "
        f"{dev.platform} {dev.device_kind!r} x{len(devices)}; "
        f"compile cache {cache}")
    ctx = Context(cell=name, config=cell.config, traffic=cell.traffic,
                  seed=seed, devices=devices, timer=timer,
                  rng=np.random.default_rng(seed))
    driver = spec_lib.load_module("drivers", cell.traffic["driver"], bench_dir)
    sut = driver.setup(ctx)
    try:
        return _measure(cell, sut, name, seed, seconds, trace, t_start, timer,
                        counter, devices, bench_dir)
    finally:
        sut.free_program()


def _measure(cell, sut, name, seed, seconds, trace, t_start, timer, counter,
             devices, bench_dir):
    dev = devices[0]
    trace_dir = None
    if trace:
        trace_dir = str(OUT_DIR / "trace" / f"{name}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    # Set-up's garbage goes now; the collector's policy is the program's.
    gc.collect()
    setup_s = time.perf_counter() - t_start
    print("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in timer.parts.items())
          + f"; total {setup_s:.3f} s", flush=True)
    counter.active = True
    with _traced(trace_dir) as spans:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.window"):
            records = sut.window(seconds)
    counter.active = False
    print("window: " + json.dumps(_window_summary(records, counter.count)),
          flush=True)
    if counter.names:
        log(f"lowered inside the window: {counter.names}")
    mem = memory_peak_bytes(devices)

    sut.free_program()
    gc.collect()
    from bench import reference

    t0 = time.perf_counter()
    A, bs = sut.reference_inputs()
    X_ref = reference.solve(A, bs, devices)
    ref_s = time.perf_counter() - t0
    gap = compare(sut.answers, X_ref)
    truth = compare(sut.answers, np.asarray(sut.X_true, np.float64))
    print(f"reference: {ref_s:.3f} s; answers {len(sut.answers)}; "
          f"worst gap to x_j by construction {truth:.3e}", flush=True)
    checks = checks_of(cell, records, gap)
    sut.release()

    run = Run(cell=name, config=cell.config, traffic=cell.traffic,
              chips=cell.chips, device_kind=dev.device_kind, setup_s=setup_s,
              records=records, spans=spans)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": is_correct(checks),
              "attempted": int(records["attempted"]),
              "failed": int(records["failed"])}
    if trace:
        from bench import trace as trace_lib

        run.trace = trace_lib.load(trace_lib.find_xplane(trace_dir))
        result["metrics"] = read_metrics(cell.per_layer, run, bench_dir)
        device["busy_s"] = run.trace.mean_busy_s()
        device["window_s"] = run.trace.window_s
        result["device"] = device
        result["breakdown"] = {
            "device_ops": trace_lib.top_ops(run.trace),
            "idle_gaps": trace_lib.idle_gaps(run.trace),
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = read_metrics(cell.end_to_end, run, bench_dir)
        result["device"] = device
    result["checks"] = checks
    for cname, c in checks.items():
        log(f"check {cname}: {c['value']} (limit {c['limit']})")
    return result


@contextlib.contextmanager
def _traced(trace_dir):
    """The profiler and the program's span tracer, on in a traced run."""
    if trace_dir is None:
        yield None
        return
    import jax

    from repro.obs import tracing

    events: list = []
    # Host annotations and runtime events, no Python function tracing: it
    # would slow the host path it is meant to watch.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with tracing() as tracer:
            yield events
    finally:
        jax.profiler.stop_trace()
    events.extend(tracer.chrome_trace().get("traceEvents", []))


def _window_summary(records: dict, compiles: int) -> dict:
    out = {k: v for k, v in records.items()
           if not isinstance(v, (list, dict))}
    out["compiles_in_window"] = compiles
    return out
