"""The readers of the program's scopes and spans (bench/scopes.py and the
metrics that use it), on profiles written here as XSpace protobufs: the
same files, read the same way, as a traced run on the chip leaves."""
import json
from pathlib import Path

import pytest

from bench import harness, scopes, trace as trace_lib, work

MS = 1_000_000  # ns
CONFIG = {"m": 1 << 20, "n": 1000, "dtype": "float32", "sketch_rows": 4000}
REQUEST = {"batch_size": 1, "queued_s": 0.01, "latency_s": 0.1}


def _xspace(ops, host, window_ns) -> str:
    """An XSpace text proto: ``ops`` are (HLO text, tf_op or None, start
    ns, end ns) on chip 0's ``XLA Ops`` line, ``host`` (line, name, start,
    end) on the host plane, and ``bench.window`` spans [0, window_ns]."""
    names = {}

    def meta(name, tf_op=None):
        if (name, tf_op) not in names:
            stats = (f' stats {{ metadata_id: 1 str_value: "{tf_op}:" }}'
                     if tf_op else "")
            names[name, tf_op] = (len(names) + 1, json.dumps(name), stats)
        return names[name, tf_op][0]

    def event(mid, s, e):
        return (f"events {{ metadata_id: {mid} offset_ps: {int(s) * 1000}"
                f" duration_ps: {int(e - s) * 1000} }}")

    lines = {"python": [event(meta("bench.window"), 0, window_ns)]}
    for line, name, s, e in host:
        lines.setdefault(line, []).append(event(meta(name), s, e))
    host_lines = " ".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 0 {" ".join(evs)} }}'
        for i, (ln, evs) in enumerate(lines.items(), 1))
    host_meta = " ".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: {n} }} }}"
        for i, n, _ in names.values())
    names.clear()
    dev_events = " ".join(event(meta(t, op), s, e) for t, op, s, e in ops)
    dev_meta = " ".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: {n}{st} }} }}"
        for i, n, st in names.values())
    return (
        f'planes {{ id: 1 name: "/host:CPU" {host_lines} {host_meta} }}'
        f' planes {{ id: 2 name: "/device:TPU:0" lines {{ id: 1'
        f' name: "XLA Ops" timestamp_ns: 0 {dev_events} }} {dev_meta}'
        ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }')


def _run(tmp_path, monkeypatch, cell, text, records):
    """The harness's Run of a traced run whose profile is ``text``, with
    the profile where the harness leaves it while the readers run."""
    from jax.profiler import ProfileData

    out = tmp_path / ".bench_out"
    path = out / "trace" / f"{cell}-7" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    xplane = path / "host.xplane.pb"
    xplane.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(harness, "OUT_DIR", out)
    trace = trace_lib.load(str(xplane))
    return harness.Run(cell=cell, config=dict(CONFIG), traffic={}, chips=1,
                       device_kind="TPU v5 lite", setup_s=1.0,
                       records=records, trace=trace)


def _read(run):
    got = harness.read_metrics(harness.spec_lib.cell(run.cell).per_layer, run)
    return {k: v["value"] for k, v in got.items()}


def _fusion(k):
    return f"%fusion.{k} = f32[1000]{{0}} fusion(f32[1048576,1000]{{1,0}} %p)"


WHILE = "%while.3 = (s32[], f32[1000]{0}) while((s32[], f32[1000]{0}) %t)"
REFINE = "jit(iterative_sketching)/jit(heavy_ball_refine)/refine"


def test_a_scope_is_a_whole_component_of_the_path():
    assert scopes.scope_path(f"{REFINE}/while/body/dot_general:") == (
        "jit(iterative_sketching)", "jit(heavy_ball_refine)", "refine",
        "while", "body", "dot_general")
    assert scopes.scope_path("") == ()
    ops = [scopes.ScopedOp(p, 0, MS) for p in [
        ("jit(_solve_many)", "lsqr", "vmap()", "while", "body", "dot"),
        ("jit(f)", "jit(lsqr)", "mul"),  # a jitted function named lsqr
        ("jit(f)", "certify_block", "add"),
        ("jit(f)", "lsqr.<locals>.body")]]
    scoped = scopes.Scoped(window=(0, 10 * MS), ops={0: ops})
    assert scoped.count("lsqr") == 1 and scoped.count("certify") == 0
    assert scoped.seconds("lsqr") == pytest.approx(1e-3)


def test_a_while_op_is_not_counted_twice(tmp_path, monkeypatch):
    ops = [("%countsketch_apply.2 = f32[4096,1000]{1,0} custom-call(), "
            'custom_call_target="tpu_custom_call"', None, 0, 30 * MS),
           (WHILE, f"{REFINE}/while", 40 * MS, 140 * MS),
           (_fusion(17), f"{REFINE}/while/body/dot_general", 40 * MS,
            80 * MS),
           (_fusion(19), f"{REFINE}/while/body/dot_general", 80 * MS,
            120 * MS),
           (_fusion(7), f"{REFINE}/dot_general", 140 * MS, 150 * MS)]
    run = _run(tmp_path, monkeypatch, "fig3.fresh", _xspace(ops, [], 200 * MS),
               {"window_s": 0.2, "solves": [{"itn": 2}]})
    got = _read(run)
    # the two body fusions and the final residual; not the while around them
    assert got["refine_ms"] == pytest.approx(90.0)
    assert got["sketch_ms"] == pytest.approx(30.0)
    assert 0 < got["refine_roofline"] <= 100


def test_ops_of_one_name_are_told_apart_by_their_metadata(
        tmp_path, monkeypatch):
    """On the v5e one HLO text can stand for ops of two programs: the
    triangular solve of the batched LSQR and that of the certificate."""
    text = "%custom-call.2 = f32[8,128,128]{1,2,0} custom-call(%p)"
    ops = [(text, "jit(_solve_many)/jit(_solve_triangular)/triangular_solve",
            0, 2 * MS),
           (text, "jit(_certify_batch)/certify/jit(_solve_triangular)/"
            "triangular_solve", 5 * MS, 6 * MS)]
    host = [("pump", "serve.dispatch.session", 0, 10 * MS)]
    run = _run(tmp_path, monkeypatch, "fig3.serve",
               _xspace(ops, host, 10 * MS),
               {"window_s": 0.01, "requests": [REQUEST]})
    scoped = scopes.of_run(run)
    assert scoped.seconds("certify") == pytest.approx(1e-3)
    assert _read(run)["certify_device_ms.serve"] == pytest.approx(1.0)


def test_refine_roofline_reads_100_at_the_least_time(tmp_path, monkeypatch):
    itn = 24
    one, bound = work.least_seconds(
        {"flops": 4 * CONFIG["m"] * CONFIG["n"],
         "bytes": (CONFIG["m"] * CONFIG["n"] + CONFIG["m"]) * 4},
        work.peaks("TPU v5 lite"))
    assert bound == "hbm"
    took_ns = round(one * (itn + 1) * 1e9)
    ops = [(_fusion(17), f"{REFINE}/while/body/dot_general", 0, took_ns)]
    run = _run(tmp_path, monkeypatch, "fig3.fresh",
               _xspace(ops, [], took_ns + MS),
               {"window_s": 0.2, "solves": [{"itn": itn}]})
    assert _read(run)["refine_roofline"] == pytest.approx(100.0, rel=1e-6)


def _serve_ops():
    """Two batches: LSQR 60 ms and the certificate 6 ms each."""
    ops = []
    for t0 in (100 * MS, 500 * MS):
        ops += [(_fusion(3), "jit(_solve_many)/lsqr/vmap()/while/body/dot",
                 t0, t0 + 60 * MS),
                (_fusion(70), "jit(_certify_batch)/certify/dot",
                 t0 + 70 * MS, t0 + 76 * MS)]
    return ops


def _pump(t0, t1):
    return [("pump", "serve.dispatch.session", t0, t1),
            ("pump", "serve.solve", t0 + MS, t0 + 61 * MS)]


def test_serve_readers_per_batch_and_pump_idle(tmp_path, monkeypatch):
    host = (_pump(50 * MS, 200 * MS) + _pump(450 * MS, 600 * MS)
            + [("python", "bench.submit", 10 * MS, 11 * MS)])
    run = _run(tmp_path, monkeypatch, "fig3.serve",
               _xspace(_serve_ops(), host, 1000 * MS),
               {"window_s": 1.0, "requests": [REQUEST]})
    got = _read(run)
    assert got["lsqr_ms.serve"] == pytest.approx(60.0)
    assert got["certify_device_ms.serve"] == pytest.approx(6.0)
    # inside each dispatch span (150 ms) the chip runs 60 + 6 ms of ops
    assert got["pump_idle.serve"] == pytest.approx(100 * 2 * 84 / 1000)
    assert got["pump_idle.serve"] <= got["device_idle.serve"]
    assert got["device_idle.serve"] == pytest.approx(100 * (1 - 0.132))


def test_pump_idle_never_exceeds_device_idle(tmp_path, monkeypatch):
    # dispatch spans that cover the whole window, idle stretches included
    host = [("pump", "serve.dispatch.session", 0, 1000 * MS)]
    run = _run(tmp_path, monkeypatch, "fig3.serve",
               _xspace(_serve_ops(), host, 1000 * MS),
               {"window_s": 1.0, "requests": [REQUEST]})
    got = _read(run)
    assert got["pump_idle.serve"] == pytest.approx(got["device_idle.serve"])


def test_without_scopes_or_spans_the_new_readers_read_nothing(
        tmp_path, monkeypatch):
    """A program without the scopes and spans, as the parent's is."""
    plain = [(t, None, s, e) for t, _, s, e in _serve_ops()]
    run = _run(tmp_path, monkeypatch, "fig3.serve",
               _xspace(plain, [("python", "bench.wait", 0, MS)], 1000 * MS),
               {"window_s": 1.0, "requests": [REQUEST]})
    got = _read(run)
    assert "device_idle.serve" in got
    for name in ("lsqr_ms.serve", "certify_device_ms.serve",
                 "pump_idle.serve"):
        assert name not in got
    run = _run(tmp_path / "f", monkeypatch, "fig3.fresh",
               _xspace([(_fusion(17), None, 0, MS)], [], 2 * MS),
               {"window_s": 0.1, "solves": [{"itn": 3}]})
    got = _read(run)
    assert "refine_ms" not in got and "refine_roofline" not in got
    assert got["iterations"] == 3


def test_a_profile_of_another_window_is_not_read(tmp_path, monkeypatch):
    ops = [(_fusion(17), f"{REFINE}/while/body/dot_general", 0, MS)]
    run = _run(tmp_path, monkeypatch, "fig3.fresh", _xspace(ops, [], 2 * MS),
               {"window_s": 0.1, "solves": [{"itn": 3}]})
    assert scopes.of_run(run) is not None
    run.trace.window = (0.0, 3.0 * MS)  # the run's own profile is gone
    assert scopes.of_run(run) is None
    run.trace = None  # an untraced run
    assert scopes.of_run(run) is None


# Cut from one traced run of each cell on a TPU v5e (seeds 4000013101 and
# 4000013201, 4 s windows): the device ops of chip 0's "XLA Ops" line and
# the bench.* and repro.obs host events inside the cut, with bench.window
# set to the cut; of each op's metadata only its id, the instruction's name
# and opcode (and custom-call target) and its tf_op stat are kept.
FIXTURES = Path(__file__).with_name("fixtures")


def _recorded(tmp_path, monkeypatch, cell, records):
    text = (FIXTURES / f"{cell.replace('.', '_')}_v5e.xplane.txt").read_text()
    return _run(tmp_path, monkeypatch, cell, text, records)


def test_readers_on_a_recorded_v5e_solve(tmp_path, monkeypatch):
    """One whole solve, of 22 iterations."""
    run = _recorded(tmp_path, monkeypatch, "fig3.fresh",
                    {"window_s": 0.589, "solves": [{"itn": 22}]})
    got = _read(run)
    assert set(got) == {"sketch_roofline", "sketch_ms", "iterations",
                        "device_idle.solve", "refine_ms", "refine_roofline"}
    # as the chip's own readers read it (4 s and 20 s windows): ~11.3 ms a
    # pass over A, at 45.5 % of the least time
    assert got["refine_ms"] == pytest.approx(259.144, abs=1e-3)
    assert got["refine_roofline"] == pytest.approx(45.498, abs=1e-3)
    assert got["sketch_ms"] == pytest.approx(307.820, abs=1e-3)
    scoped = scopes.of_run(run)
    # the kernel's pallas_call name is a component of its ops' path
    assert scoped.count("countsketch_apply") == 2
    assert scoped.count("lsqr") == scoped.count("certify") == 0


def test_readers_on_recorded_v5e_batches(tmp_path, monkeypatch):
    """Three whole batches of the served mix."""
    run = _recorded(tmp_path, monkeypatch, "fig3.serve",
                    {"window_s": 0.43, "requests": [REQUEST]})
    got = _read(run)
    assert {"lsqr_ms.serve", "certify_device_ms.serve", "pump_idle.serve",
            "device_idle.serve"} <= set(got)
    assert got["lsqr_ms.serve"] == pytest.approx(68.012, abs=1e-3)
    assert got["certify_device_ms.serve"] == pytest.approx(6.317, abs=1e-3)
    assert got["pump_idle.serve"] == pytest.approx(21.768, abs=1e-3)
    assert got["pump_idle.serve"] <= got["device_idle.serve"]
    # the pump's spans: one of each per batch
    counts = {}
    for h in run.trace.host:
        counts[h.name] = counts.get(h.name, 0) + 1
    for name in ("serve.dispatch.session", "serve.solve", "serve.certify",
                 "serve.collect", "serve.resolve"):
        assert counts[name] == 3
