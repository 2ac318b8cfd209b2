"""repro.obs: the metrics registry, the span tracer, and the exporters.

Three layers of coverage:

- unit: instruments (counter/gauge/histogram), the StatsDict mirror, span
  nesting/depth bookkeeping, activation semantics (env flag aside);
- integration: ``lstsq(..., trace=True)`` / ``stream_lstsq`` / a cluster
  solve with an injected kill / a ``SolveService`` batch each produce a
  complete, valid Chrome-trace timeline;
- contracts: thread-safety under concurrent submit, tracing-disabled
  overhead within noise of a fully stripped build (the hard ≤1.05x gate
  lives in benchmarks/perf_gate.py — here we only pin "same order").
"""
import json
import pickle
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import ClusterSpec
from repro.cluster.faults import FaultPlan, KillWorker
from repro.core.lstsq import lstsq
from repro.obs import trace as obs_trace
from repro.obs.export import json_snapshot, prometheus_text, save_chrome_trace
from repro.obs.metrics import MetricsRegistry
from repro.serve import SolveService
from repro.streaming.solve import stream_lstsq


@pytest.fixture(autouse=True)
def _no_tracer_leak():
    """Every test starts and ends with tracing off."""
    obs_trace.disable()
    yield
    obs_trace.disable()


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def _problem(m=256, n=16, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((m, n)).astype(dtype))
    b = jnp.asarray(rng.standard_normal(m).astype(dtype))
    return A, b


# ---------------------------------------------------------------------------
# metrics


def test_counter_gauge_histogram():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("t.c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("t.g")
    g.set(7)
    g.inc(-2)
    assert g.value == 5
    h = reg.histogram("t.h")
    h.observe(2e-4)   # second bucket (3e-4)
    h.observe(1e9)    # +inf overflow
    snap = h.snapshot()
    assert snap["count"] == 2
    assert snap["counts"][1] == 1
    assert snap["counts"][-1] == 1
    assert snap["sum"] == pytest.approx(2e-4 + 1e9)


def test_registry_get_or_create_is_stable():
    reg = MetricsRegistry(enabled=True)
    assert reg.counter("x") is reg.counter("x")
    assert reg.gauge("y") is reg.gauge("y")
    snap = reg.snapshot()
    assert "x" in snap["counters"] and "y" in snap["gauges"]


def test_disabled_registry_hands_out_nulls():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("nope")
    c.inc(10)
    assert c.value == 0
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_stats_dict_is_a_plain_dict_to_tests():
    reg = MetricsRegistry(enabled=True)
    d = reg.stats_dict("ns", {"a": 0, "b": 0})
    d["a"] += 3
    d["b"] = 2
    assert d == {"a": 3, "b": 2}          # exact-equality pins keep working
    assert sorted(d) == ["a", "b"]
    assert reg.counter("ns.a").value == 3
    assert reg.gauge("ns.a.last").value == 3
    # two instances aggregate into the SAME registry counter
    d2 = reg.stats_dict("ns", {"a": 0})
    d2["a"] += 1
    assert reg.counter("ns.a").value == 4
    # pickles as a plain dict (cluster checkpoints must not drag the
    # registry through pickle)
    back = pickle.loads(pickle.dumps(d))
    assert type(back) is dict and back == {"a": 3, "b": 2}


def test_metrics_thread_safety():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("mt.c")
    d = reg.stats_dict("mt", {"hits": 0})
    lock = threading.Lock()

    def work():
        for _ in range(1000):
            c.inc()
            with lock:  # dict += is not atomic; the registry mirror is
                d["hits"] += 1

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert d["hits"] == 8000
    assert reg.counter("mt.hits").value == 8000


# ---------------------------------------------------------------------------
# trace core


def test_span_is_noop_when_disabled():
    assert not obs_trace.enabled()
    sp = obs_trace.span("anything", a=1)
    assert not sp  # falsy → call sites skip attr extraction
    with sp as s:
        s.set(b=2)  # must not raise
    obs_trace.instant("nothing")  # must not raise
    assert obs_trace.current() is None


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each one
    made, with its arguments, and whether it is open."""

    made: list = []

    def __init__(self, *args, **kw):
        self.args, self.kw, self.state = args, kw, "made"
        _Annotation.made.append(self)

    def __enter__(self):
        self.state = "open"
        return self

    def __exit__(self, *exc):
        self.state = "closed"
        return False


def test_span_annotates_the_profile_only_while_tracing(monkeypatch):
    monkeypatch.setattr(_Annotation, "made", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    sp = obs_trace.span("off", a=1)
    assert sp is obs_trace._NOOP  # the shared no-op, nothing built
    with sp:
        pass
    assert _Annotation.made == []

    def traced(x):
        with obs_trace.span("traced"):
            return x + 1

    with obs_trace.tracing():
        jax.jit(traced)(1.0)
        assert _Annotation.made == []  # suppressed under jit tracing
        with obs_trace.span("serve.solve", k=3):
            (ann,) = _Annotation.made
            # the name alone: the profile's event is named as the span
            assert ann.args == ("serve.solve",) and ann.kw == {}
            assert ann.state == "open"
    assert ann.state == "closed"


def test_spans_land_in_the_profile_on_their_thread(tmp_path):
    from jax.profiler import ProfileData

    def worker():
        with obs_trace.span("worker.region"):
            time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.tracing(), obs_trace.span("caller.region", k=1):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    lines = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                lines.setdefault(ev.name, set()).add((plane.name, i))
    assert len(lines["caller.region"]) == 1
    assert len(lines["worker.region"]) == 1
    assert lines["caller.region"] != lines["worker.region"]


def test_span_nesting_depth_and_order():
    with obs_trace.tracing() as tr:
        with obs_trace.span("outer", k=1) as outer:
            with obs_trace.span("inner"):
                obs_trace.instant("tick", v=2)
            outer.set(done=True)
    spans = {e["name"]: e for e in tr.events if e.get("ph") == "X"}
    assert spans["outer"]["depth"] == 0
    assert spans["inner"]["depth"] == 1
    assert spans["outer"]["args"] == {"k": 1, "done": True}
    # inner is contained in outer's [ts, ts+dur] window
    o, i = spans["outer"], spans["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
    (tick,) = [e for e in tr.events if e.get("ph") == "i"]
    assert tick["name"] == "tick" and tick["depth"] == 2
    assert not obs_trace.enabled()  # tracing() deactivated on exit


def test_tracing_joins_active_tracer():
    with obs_trace.tracing() as tr1:
        with obs_trace.tracing() as tr2:
            assert tr2 is tr1
        assert obs_trace.enabled()  # inner exit must not deactivate
    assert not obs_trace.enabled()


def test_chrome_trace_json_is_valid():
    with obs_trace.tracing() as tr:
        with obs_trace.span("a", shape=(3, 4)):
            obs_trace.instant("b")
    obj = tr.chrome_trace()
    text = json.dumps(obj)  # must be serializable (tuples etc. included)
    parsed = json.loads(text)
    assert parsed["displayTimeUnit"] == "ms"
    events = parsed["traceEvents"]
    assert any(e["ph"] == "M" for e in events)  # thread_name metadata
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0


def test_solve_scope_semantics():
    # flag=True owns and deactivates
    sc = obs_trace.solve_scope(True)
    with sc:
        assert obs_trace.enabled()
        with obs_trace.span("s"):
            pass
    assert not obs_trace.enabled()
    # flag=None observes an enclosing tracer without owning it
    with obs_trace.tracing():
        with obs_trace.solve_scope(None) as sc2:
            with obs_trace.span("t"):
                pass
        assert obs_trace.enabled()
        res = sc2.attach(_FakeRes())
        assert res.timeline is not None
        assert "t" in res.timeline.names()
    # flag=None with nothing active: attach is a no-op
    with obs_trace.solve_scope(None) as sc3:
        pass
    r = _FakeRes()
    assert sc3.attach(r) is r


class _FakeRes:
    timeline = None

    def _replace(self, **kw):
        out = _FakeRes()
        out.timeline = kw.get("timeline")
        return out


def test_stripped_swaps_and_restores():
    real_span = obs_trace.span
    with obs_trace.stripped():
        assert obs_trace.span is not real_span
        with obs_trace.tracing() as tr:
            with obs_trace.span("invisible"):
                pass
        assert tr.events == [] or all(
            e["ph"] == "M" for e in tr.events
        )
    assert obs_trace.span is real_span


def test_threads_get_distinct_tids():
    with obs_trace.tracing() as tr:
        def work():
            with obs_trace.span("child_thread"):
                pass
        t = threading.Thread(target=work, name="obs-test-worker")
        t.start()
        t.join()
        with obs_trace.span("main_thread"):
            pass
    spans = {e["name"]: e for e in tr.events if e.get("ph") == "X"}
    assert spans["child_thread"]["tid"] != spans["main_thread"]["tid"]
    names = {
        e["args"]["name"] for e in tr.events if e.get("ph") == "M"
    }
    assert "obs-test-worker" in names


# ---------------------------------------------------------------------------
# integration: solver


def test_lstsq_untraced_has_no_timeline(key):
    A, b = _problem()
    res = lstsq(A, b, key)
    assert res.timeline is None
    assert not obs_trace.enabled()


def test_lstsq_traced_attaches_nested_timeline(key):
    A, b = _problem()
    res = lstsq(A, b, key, trace=True)
    tl = res.timeline
    assert tl is not None
    names = tl.names()
    assert names[-1] == "lstsq"  # complete events close outermost-last
    assert "lstsq.select" in names and "lstsq.solve" in names
    root = [s for s in tl.spans() if s["name"] == "lstsq"][0]
    assert root["depth"] == 0 and root["args"]["method"] == res.method
    solve = [s for s in tl.spans() if s["name"] == "lstsq.solve"][0]
    assert solve["depth"] == 1 and "itn" in solve["args"]
    json.loads(json.dumps(tl.chrome_trace()))  # valid chrome trace
    assert "lstsq" in str(tl)  # renders
    assert not obs_trace.enabled()  # per-call scope released the tracer


def _row_sharded(A, b):
    """(A, b, mesh) row-sharded over a one-device mesh: the distributed
    driver's own code path, on the one device a test process has."""
    from repro.core.distributed import shard_rows
    from repro.sharding import make_mesh

    mesh = make_mesh((1,), ("data",))
    return (*shard_rows(mesh, ("data",), A, b), mesh)


def test_sketched_lstsq_traced_records_its_span(key):
    from repro.core.distributed import sketched_lstsq

    A, b, mesh = _row_sharded(*_problem(m=512, n=8))
    with obs_trace.tracing() as tr:
        res = sketched_lstsq(A, b, key, mesh=mesh)
    spans = [s for s in tr.timeline().spans() if s["name"] == "sketched_lstsq"]
    assert len(spans) == 1
    assert spans[0]["args"] == {"chips": 1, "m": 512, "n": 8,
                                "itn": int(res.itn)}
    assert res.method == "saa"


def test_sketched_lstsq_untraced_costs_nothing(key, monkeypatch):
    """Tracing off: no span object is made and nothing blocks."""
    from repro.core.distributed import sketched_lstsq

    A, b, mesh = _row_sharded(*_problem(m=512, n=8))
    made, blocked = [], []
    monkeypatch.setattr(obs_trace, "_Span",
                        lambda *a, **k: made.append(a))
    monkeypatch.setattr(obs_trace.jax, "block_until_ready",
                        lambda x: blocked.append(x) or x)
    res = sketched_lstsq(A, b, key, mesh=mesh)
    assert not made and not blocked
    assert res.timeline is None


def test_certified_trace_shows_rungs_and_probes(key):
    A, b = _problem(m=512, n=8)
    res = lstsq(A, b, key, accuracy="certified", trace=True)
    names = res.timeline.names()
    assert "certified.rung" in names
    assert "certify.probe" in names
    assert "factor.build" in names  # built eagerly, outside jit
    rungs = [s for s in res.timeline.spans() if s["name"] == "certified.rung"]
    assert all("passed" in r["args"] for r in rungs)
    assert rungs[-1]["args"]["passed"] is True


# ---------------------------------------------------------------------------
# integration: streaming + cluster


def test_streamed_trace_has_pass_structure(key):
    A, b = _problem(m=512, n=8)
    res = stream_lstsq(np.asarray(A), np.asarray(b), key, tile_rows=128,
                       trace=True)
    names = set(res.timeline.names())
    assert {"stream_lstsq", "stream.pass1", "stream.tile",
            "factor.qr", "stream.solve"} <= names
    tiles = [s for s in res.timeline.spans() if s["name"] == "stream.tile"]
    assert len(tiles) == 4  # 512 rows / 128-row tiles
    assert not obs_trace.enabled()


def test_cluster_kill_trace_shows_recovery(key, tmp_path):
    A, b = _problem(m=512, n=8)
    plan = FaultPlan(KillWorker(worker=1, at_tile=1))
    spec = ClusterSpec(num_workers=3, tile_rows=64, checkpoint_every=1,
                       ckpt_dir=str(tmp_path), faults=plan)
    res = stream_lstsq(np.asarray(A), np.asarray(b), key, tile_rows=64,
                       cluster=spec, trace=True)
    assert plan.fired
    names = set(res.timeline.names())
    assert {"cluster.pass1", "cluster.task", "cluster.merge",
            "cluster.recover", "cluster.reassign",
            "cluster.restore"} <= names
    # the kill's task range was restored from its checkpoint watermark
    (restore,) = [e for e in res.timeline.instants()
                  if e["name"] == "cluster.restore"]
    assert restore["args"]["watermark"] > restore["args"]["start"]
    # worker tasks land on their worker threads, not the caller's
    task_tids = {s["tid"] for s in res.timeline.spans()
                 if s["name"] == "cluster.task"}
    assert len(task_tids) >= 2
    json.loads(json.dumps(res.timeline.chrome_trace()))


# ---------------------------------------------------------------------------
# integration: serve


def test_serve_batch_trace_and_counter_consistency(key):
    A, b = _problem(m=768, n=12)
    svc = SolveService(key, max_delay_s=0.0, default_rtol=1e-8)
    n_req = 12
    errs = []

    with obs_trace.tracing() as tr:
        def submit_some(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(n_req // 4):
                    svc.submit(A, jnp.asarray(rng.standard_normal(768)),
                               mode="session")
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        threads = [threading.Thread(target=submit_some, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        svc.flush()

    names = {e["name"] for e in tr.events}
    assert {"serve.submit", "serve.dispatch.session",
            "serve.solve", "serve.certify", "serve.collect",
            "serve.resolve"} <= names
    submits = [e for e in tr.events if e["name"] == "serve.submit"]
    assert len(submits) == n_req
    st = svc.stats()
    assert st["requests"] == n_req
    assert st["ok"] + st["rejected"] == n_req  # consistent snapshot
    assert st["pending"] == 0
    # queue-vs-dispatch breakdown: every dispatch span nests solve+certify
    disp = [e for e in tr.events if e["name"] == "serve.dispatch.session"]
    solve = [e for e in tr.events if e["name"] == "serve.solve"]
    assert disp and solve
    assert min(s["depth"] for s in solve) > min(d["depth"] for d in disp)


def test_serve_stats_snapshot_under_concurrent_load(key):
    """stats() polled while submits and pumps race stays self-consistent."""
    A, b = _problem(m=768, n=12)
    svc = SolveService(key, max_delay_s=0.0, default_rtol=1e-8)
    svc.start()
    bad = []
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            st = svc.stats()
            if st["ok"] + st["rejected"] > st["requests"]:
                bad.append(dict(st))
            time.sleep(0.0002)

    poller = threading.Thread(target=poll)
    poller.start()
    try:
        rng = np.random.default_rng(1)
        futs = [svc.submit(A, jnp.asarray(rng.standard_normal(768)),
                           mode="session")
                for _ in range(24)]
        for f in futs:
            f.result(timeout=60)
    finally:
        stop.set()
        poller.join()
        svc.stop()
    assert not bad, f"inconsistent stats snapshots: {bad[:3]}"
    st = svc.stats()
    assert st["requests"] == 24 and st["ok"] + st["rejected"] == 24


# ---------------------------------------------------------------------------
# exporters


def test_prometheus_text_format():
    reg = MetricsRegistry(enabled=True)
    reg.counter("unit.requests").inc(3)
    reg.gauge("unit.depth").set(2)
    h = reg.histogram("unit.lat_s")
    for v in (2e-4, 5e-3, 99.0):
        h.observe(v)
    txt = prometheus_text(reg)
    lines = txt.strip().splitlines()
    assert "# TYPE repro_unit_requests counter" in lines
    assert "repro_unit_requests 3" in lines
    assert "repro_unit_depth 2" in lines
    # cumulative buckets end at the total count, +Inf line included
    assert 'repro_unit_lat_s_bucket{le="+Inf"} 3' in lines
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in lines
            if ln.startswith("repro_unit_lat_s_bucket")]
    assert cums == sorted(cums)
    assert "repro_unit_lat_s_count 3" in lines


def test_json_snapshot_and_save_chrome_trace(tmp_path):
    reg = MetricsRegistry(enabled=True)
    reg.counter("snap.n").inc()
    snap = json_snapshot(reg)
    assert snap["counters"]["snap.n"] == 1 and "ts_unix" in snap
    with obs_trace.tracing() as tr:
        with obs_trace.span("saved"):
            pass
    p = save_chrome_trace(tr, str(tmp_path / "trace.json"))
    loaded = json.load(open(p))
    assert any(e["name"] == "saved" for e in loaded["traceEvents"])


# ---------------------------------------------------------------------------
# overhead contract (loose here; the 1.05x machine gate is in benchmarks)


def test_disabled_span_overhead_same_order():
    """The disabled path (global check + shared no-op) must stay within
    small constant factors of a fully stripped build.  The tight ≤1.05x
    end-to-end gate runs on real solves in benchmarks/perf_gate.py; this
    guards against the disabled path growing real work (allocation,
    locks, formatting)."""
    N = 50_000

    def disabled_loop():
        t0 = time.perf_counter()
        for _ in range(N):
            with obs_trace.span("x", a=1):
                pass
        return time.perf_counter() - t0

    def stripped_loop():
        with obs_trace.stripped():
            t0 = time.perf_counter()
            for _ in range(N):
                with obs_trace.span("x", a=1):
                    pass
            return time.perf_counter() - t0

    disabled = min(disabled_loop() for _ in range(3))
    stripped_t = min(stripped_loop() for _ in range(3))
    # per-call cost of the disabled path, in ns — the real contract
    per_call_ns = (disabled / N) * 1e9
    assert per_call_ns < 2000, f"disabled span costs {per_call_ns:.0f}ns/call"
    assert disabled < max(stripped_t * 10, 0.05), (
        f"disabled={disabled:.4f}s stripped={stripped_t:.4f}s"
    )
