"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; each is a file of its own
(``configs[].file``, ``bench/traffic/<mix>.json``), and so are the cell's
limits (``bench/workloads/<cell>.json``), its driver
(``bench/drivers/<driver>.py``) and each metric's reader
(``bench/metrics/<metric>.py``).  Adding a cell, a mix or a metric adds
files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH) -> Cell:
    """The cell ``name`` with everything it names loaded."""
    spec = load_benchmark(root)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((Path(root) / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (Path(bench_dir) / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (Path(bench_dir) / "workloads" / f"{name}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    # A per-layer metric names the cells it reads something in.
    per_layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def load_module(kind: str, name: str, bench_dir: Path = BENCH):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    mod_name = f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
