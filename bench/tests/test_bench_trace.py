"""Trace reduction: the recorded v5e fixture, and the loader on a trace
recorded here."""
import json
from pathlib import Path

import pytest

from bench import harness, trace as trace_lib

FIXTURE = Path(__file__).with_name("fixtures") / "fig3_fresh_trace.json"


def _run(trace, solves, config):
    return harness.Run(
        cell="fig3.fresh", config=config, traffic={}, chips=1,
        device_kind="TPU v5 lite", setup_s=1.0,
        records={"window_s": trace.window_s, "solves": solves}, trace=trace)


def test_short_name_keeps_the_instruction_and_target():
    text = ('%countsketch_apply.2 = f32[4096,1000]{1,0} custom-call(s32[1,8]'
            ' %bitcast.654), custom_call_target="tpu_custom_call", x')
    assert trace_lib.short_name(text) == "countsketch_apply.2 [tpu_custom_call]"
    # an operand named after the kernel is not the kernel
    assert trace_lib.short_name(
        "%slice.1 = f32[4000,1000] slice(f32[4096,1000] %countsketch_apply.2)"
    ) == "slice.1"
    assert trace_lib.is_control("while.16") and not trace_lib.is_control("w.1")


def test_busy_is_the_union_of_op_intervals():
    ops = [trace_lib.Op("a", 0, 10), trace_lib.Op("b", 5, 20),
           trace_lib.Op("c", 30, 40)]
    t = trace_lib.Trace(window=(0, 100), ops={0: ops}, host=[])
    assert t.busy_s(0) == pytest.approx(30e-9)
    gaps = trace_lib.idle_gaps(t)
    assert [g[1] for g in gaps] == pytest.approx([60e-9, 10e-9])
    assert gaps[0][0] == "host idle"


def test_idle_gap_named_by_the_harness_annotation():
    ops = [trace_lib.Op("a", 0, 10), trace_lib.Op("b", 50, 60)]
    host = [trace_lib.Op("bench.window", 0, 60),
            trace_lib.Op("PjRtExecute", 12, 48),
            trace_lib.Op("bench.solve", 15, 40)]
    t = trace_lib.Trace(window=(0, 60), ops={0: ops}, host=host)
    assert trace_lib.idle_gaps(t)[0] == ["bench.solve", pytest.approx(40e-9)]


def test_readers_on_the_recorded_fig3_trace():
    trace = trace_lib.from_dict(json.loads(FIXTURE.read_text()))
    config = json.loads((harness.spec_lib.BENCH / "configs" /
                         "fig3_m2p20_n1000.json").read_text())
    kernels = [o for o in trace.ops[0]
               if o.name.startswith("countsketch_apply")]
    assert kernels, "the fixture holds the CountSketch kernel's ops"
    n_solves = 1
    run = _run(trace, [{"itn": 22}] * n_solves, config)
    spec = harness.spec_lib.cell("fig3.fresh")
    got = harness.read_metrics(spec.per_layer, run)
    assert set(got) == {"sketch_roofline", "sketch_ms", "iterations",
                        "device_idle.solve"}
    k_s = sum(o.end - o.start for o in kernels) * 1e-9
    assert got["sketch_ms"]["value"] == pytest.approx(1e3 * k_s / n_solves)
    assert 0 < got["sketch_roofline"]["value"] <= 100
    assert 0 <= got["device_idle.solve"]["value"] < 100
    top = trace_lib.top_ops(trace)
    assert top[0][0].startswith("countsketch_apply")
    assert not any(trace_lib.is_control(name) for name, _ in top)


def test_load_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.solve"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace_lib.load(trace_lib.find_xplane(str(tmp_path)))
    assert t.window_s > 0
    assert any(h.name == "bench.solve" for h in t.host)
    # no TPU here: no device ops, and the readers then read nothing
    assert t.ops == {}
    assert t.mean_busy_s() == 0.0
