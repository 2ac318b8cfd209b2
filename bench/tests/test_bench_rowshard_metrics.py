"""The readers of rowshard.fresh's per-layer metrics (``lsqr_ms.rowshard``,
``lsqr_roofline.rowshard``, ``allreduce_ms.rowshard``) on four-chip
profiles written here as XSpace protobufs, and on one cut from a traced run
on a v5e host."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, scopes, trace as trace_lib, work
from bench.metrics.refine_roofline import pass_over_a

MS = 1_000_000  # ns
CELL = "rowshard.fresh"
CHIPS = 4
LSQR = "jit(_solve)/shard_map/lsqr/while/body"
SKETCH = "jit(_solve)/shard_map/sketch"


def _xspace(ops_by_chip, window_ns) -> str:
    """An XSpace text proto: ``ops_by_chip[i]`` are (HLO text, tf_op or
    None, start ns, end ns) on chip i's ``XLA Ops`` line; the host plane
    holds ``bench.window`` over [0, window_ns]."""
    planes = [
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
        "timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 duration_ps: "
        f'{window_ns * 1000} }} }} event_metadata {{ key: 1 value {{ id: 1 '
        'name: "bench.window" } } }']
    for chip, ops in enumerate(ops_by_chip):
        metas, events = {}, []
        for text, tf_op, s, e in ops:
            if (text, tf_op) not in metas:
                stats = (f' stats {{ metadata_id: 1 str_value: "{tf_op}:" }}'
                         if tf_op else "")
                metas[text, tf_op] = (len(metas) + 1, json.dumps(text), stats)
            mid = metas[text, tf_op][0]
            events.append(f"events {{ metadata_id: {mid} offset_ps: "
                          f"{int(s) * 1000} duration_ps: {int(e - s) * 1000} }}")
        meta = " ".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: {n}{st} }} }}"
            for i, n, st in metas.values())
        planes.append(
            f'planes {{ id: {chip + 2} name: "/device:TPU:{chip}" lines {{ '
            f'id: 1 name: "XLA Ops" timestamp_ns: 0 {" ".join(events)} }} '
            f'{meta} stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}')
    return " ".join(planes)


def _run(tmp_path, monkeypatch, text, solves):
    """The harness's Run of a traced run of the cell whose profile is
    ``text``, left where the harness leaves it while the readers run."""
    from jax.profiler import ProfileData

    out = tmp_path / ".bench_out"
    path = out / "trace" / f"{CELL}-7" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    xplane = path / "host.xplane.pb"
    xplane.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(harness, "OUT_DIR", out)
    cell = harness.spec_lib.cell(CELL)
    return harness.Run(cell=CELL, config=cell.config, traffic=cell.traffic,
                       chips=CHIPS, device_kind="TPU v5 lite", setup_s=1.0,
                       records={"window_s": 1.0, "solves": solves},
                       trace=trace_lib.load(str(xplane)))


def _read(run):
    cell = harness.spec_lib.cell(CELL)
    got = harness.read_metrics(cell.per_layer, run)
    return {k: v["value"] for k, v in got.items()}


def _fusion(k):
    return f"%fusion.{k} = f32[1048576]{{0}} fusion(f32[1048576,1000]{{0,1}} %p)"


def _all_reduce(name):
    """An all-reduce as the v5e names it: ``psum.<k>`` as the program wrote
    it, ``all-reduce.<k>`` where XLA combined several."""
    return f"%{name} = f32[1000]{{0}} all-reduce(f32[1000]{{0}} %p)"


def test_the_cell_lists_the_three_readers():
    names = {m["name"] for m in harness.spec_lib.cell(CELL).per_layer}
    assert names == {"allreduce_ms.rowshard", "lsqr_ms.rowshard",
                     "lsqr_roofline.rowshard"}


def test_lsqr_ms_per_solve_and_chip_and_allreduce_ms(tmp_path, monkeypatch):
    # chip i: 200 + i ms of LSQR products and 2 ms of its psums per window,
    # 1 ms of the sketch's psum and 10 ms of the sketch kernel
    chips = []
    for i in range(CHIPS):
        chips.append([
            ("%countsketch_apply.2 = f32[4000,1000]{1,0} custom-call(), "
             'custom_call_target="tpu_custom_call"', SKETCH, 0, 10 * MS),
            (_all_reduce("all-reduce.21"), SKETCH, 10 * MS, 11 * MS),
            (_fusion(17), f"{LSQR}/dot_general", 20 * MS, (220 + i) * MS),
            (_all_reduce("psum.50"), f"{LSQR}/psum", (230 + i) * MS,
             (232 + i) * MS)])
    run = _run(tmp_path, monkeypatch, _xspace(chips, 300 * MS),
               [{"itn": 12}, {"itn": 11}])
    got = _read(run)
    assert got["lsqr_ms.rowshard"] == pytest.approx((202 + 1.5) / 2)
    assert got["allreduce_ms.rowshard"] == pytest.approx(3.0 / 2)
    assert 0 < got["lsqr_roofline.rowshard"] <= 100


def test_lsqr_roofline_reads_100_at_the_least_time(tmp_path, monkeypatch):
    solves = [{"itn": 12}, {"itn": 13}]
    cfg = harness.spec_lib.cell(CELL).config
    one, bound = work.least_seconds(
        pass_over_a(cfg["m"] // CHIPS, cfg["n"], np.dtype(cfg["dtype"]).itemsize),
        work.peaks("TPU v5 lite"))
    assert bound == "hbm"
    took_ns = round(one * sum(s["itn"] + 1 for s in solves) * 1e9)
    chips = [[(_fusion(17), f"{LSQR}/dot_general", 0, took_ns)]] * CHIPS
    run = _run(tmp_path, monkeypatch, _xspace(chips, took_ns + MS), solves)
    assert _read(run)["lsqr_roofline.rowshard"] == pytest.approx(100.0,
                                                                 rel=1e-6)


def test_without_the_scope_the_lsqr_readers_read_nothing(tmp_path,
                                                         monkeypatch):
    """A program without the scopes, as the parent's is."""
    chips = [[(_fusion(17), None, 0, 50 * MS),
              (_all_reduce("psum.5"), None, 50 * MS, 51 * MS)]] * CHIPS
    run = _run(tmp_path, monkeypatch, _xspace(chips, 100 * MS), [{"itn": 3}])
    got = _read(run)
    assert "lsqr_ms.rowshard" not in got
    assert "lsqr_roofline.rowshard" not in got
    assert got["allreduce_ms.rowshard"] == pytest.approx(1.0)
    assert scopes.of_run(run).count("lsqr") == 0


# Cut from one traced run of the cell on a 2x2 v5e host (seed 4000015111,
# 20 s window): the 61st solve, 12 iterations, with the device ops of each
# chip's "XLA Ops" line and the bench.* and repro.obs host events inside
# the cut, bench.window set to the cut; of each op's metadata only its id,
# the instruction's name and opcode (and custom-call target) and its tf_op
# stat are kept.
FIXTURE = Path(__file__).with_name("fixtures") / "rowshard_fresh_v5e.xplane.txt"


def test_readers_on_a_recorded_v5e_solve(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, FIXTURE.read_text(), [{"itn": 12}])
    assert sorted(run.trace.ops) == [0, 1, 2, 3]
    got = _read(run)
    # as the chip's own readers read the whole 20 s window: ~11.1 ms a pass
    # over the chip's quarter of A, 45.8 % of the least time
    assert got["lsqr_ms.rowshard"] == pytest.approx(145.651, abs=1e-3)
    assert got["lsqr_roofline.rowshard"] == pytest.approx(45.755, abs=1e-3)
    # the sketch's combined all-reduce and LSQR's psums, 26 per solve
    assert got["allreduce_ms.rowshard"] == pytest.approx(0.3736, abs=1e-3)
    old = harness.spec_lib.load_module("metrics", "allreduce_ms").read(run)
    assert old < got["allreduce_ms.rowshard"] - 0.05  # LSQR's psums missed
    psums = run.trace.count(lambda name: name.startswith("psum."))
    assert psums == CHIPS * (2 * 12 + 1)
