"""The harness finds a new configuration, traffic mix, cell and metric by
name: adding them edits no file that is there."""
import json
import shutil
from pathlib import Path

from bench import harness, spec

ROOT = Path(__file__).resolve().parents[2]


def _copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path, tmp_path / "bench"


def _tree(path):
    return {p.relative_to(path): p.read_bytes()
            for p in sorted(path.rglob("*")) if p.is_file()}


def test_new_config_cell_and_metric_are_found_without_edits(tmp_path):
    root, bench = _copy(tmp_path)
    before = _tree(bench)
    # New files only ...
    cfg = json.loads((bench / "configs" / "fig3_m2p20_n1000.json").read_text())
    cfg.update(name="fig3_cond1e2", cond=1e2)
    (bench / "configs" / "fig3_cond1e2.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "fresh_mixed.json").write_text(json.dumps({
        "driver": "solve_loop", "loop": "closed", "clients": 1,
        "rhs_pool": 4, "solver_kwargs": {"precision": "mixed"}}))
    (bench / "workloads" / "fig3.mixed.json").write_text(
        json.dumps({"limits": {"max_rel_err": 1e-3}}))
    (bench / "metrics" / "solves_done.py").write_text(
        "def read(run):\n    return len(run.records['solves'])\n")
    # ... and entries in BENCHMARK.json.
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "fig3_cond1e2", "source": "x",
                         "file": "bench/configs/fig3_cond1e2.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "fig3.mixed", "config": "fig3_cond1e2",
                           "traffic": "fresh_mixed", "chips": 1, "why": "x"})
    for m in b["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("fig3.mixed")
    b["per_layer"].append({"name": "solves_done", "unit": "solves",
                           "better": "higher", "source": "program_counter",
                           "layer": "solver loop", "moves": "solve_s",
                           "workloads": ["fig3.mixed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.cell("fig3.mixed", root=root, bench_dir=bench)
    assert cell.config["cond"] == 1e2
    assert cell.traffic["solver_kwargs"] == {"precision": "mixed"}
    assert [m["name"] for m in cell.end_to_end] == ["solve_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["solves_done"]
    run = harness.Run(cell="fig3.mixed", config=cell.config,
                      traffic=cell.traffic, chips=1, device_kind="x",
                      setup_s=2.0, records={"window_s": 4.0,
                                            "solves": [{"itn": 3}] * 8})
    assert harness.read_metrics(cell.end_to_end + cell.per_layer, run,
                                bench) == {
        "solve_s": {"value": 0.5, "unit": "s"},
        "setup_s": {"value": 2.0, "unit": "s"},
        "solves_done": {"value": 8.0, "unit": "solves"},
    }
    after = _tree(bench)
    assert all(after[p] == data for p, data in before.items())
    assert spec.load_module("drivers", "solve_loop", bench).setup


def test_cells_name_their_files():
    b = spec.load_benchmark()
    for w in b["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.chips == w["chips"] == cell.config["chips"]
        assert "max_rel_err" in cell.limits["limits"]
        assert (spec.BENCH / "drivers" /
                f"{cell.traffic['driver']}.py").is_file()
        for m in cell.end_to_end + cell.per_layer:
            assert (spec.BENCH / "metrics" / f"{m['name']}.py").is_file()
