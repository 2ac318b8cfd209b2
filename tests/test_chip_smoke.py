"""chip_smoke.py on the CPU: its phases at a tiny size, and its refusals.

The phases run here through the same entry points as on the chip, with
the device check left out (the reference backend and interpret-mode
Pallas stand in for the compiled kernels).  The script itself must exit
non-zero, printing no result line, without a TPU or without the repo.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._import_repo()
    return mod


def test_tolerance_is_stated_from_f32_eps(smoke):
    eps = 2.0**-23
    assert smoke.tol_direct(1e4) == pytest.approx(1e4 * eps)
    assert smoke.tol_sketched(1e4) == pytest.approx(1e3 * eps)
    assert smoke.tol_sketched(1.0) == pytest.approx(100 * eps)
    # between the sound chip reading (8.6e-6) and the bf16-contracted one
    # (1.27e-3) of phase 1
    assert 8.6e-6 < smoke.tol_sketched(1e4) < 1.27e-3


def test_phase_main_tiny(smoke):
    # m·n² above the direct cutoff, so "auto" picks iterative sketching
    assert smoke.phase_main(32768, 64, 1e4, 0, compiled=False) == []


def test_phase_kinds_tiny(smoke):
    assert smoke.phase_kinds(8192, 96, 1e2, 0, compiled=False) == []


def test_phase_serve_tiny(smoke):
    assert smoke.phase_serve(8192, 128, 1e3, 0, requests=4) == []


def _patched_lstsq(monkeypatch, **override):
    """Make ``lstsq`` (as the phases import it) run with ``override``;
    ``precision`` applies to Pallas full-precision solves only."""
    import repro.core

    lstsq = repro.core.lstsq

    def patched(*args, **kw):
        if "precision" in override:
            if kw.get("backend") == "pallas" and kw.get("precision") == "full":
                kw["precision"] = override["precision"]
        else:
            kw.update(override)
        return lstsq(*args, **kw)

    monkeypatch.setattr(repro.core, "lstsq", patched)


def test_phase_main_fails_at_the_iteration_limit(smoke, monkeypatch):
    _patched_lstsq(monkeypatch, iter_lim=3)
    failures = smoke.phase_main(32768, 64, 1e4, 0, compiled=False)
    assert any("iteration limit" in f for f in failures), failures


def test_phase_kinds_fails_on_a_bf16_contracted_sketch(smoke, monkeypatch):
    """A Pallas "full" solve whose sketch is contracted in bf16 (here: run
    at mixed precision) converges, but in several times the reference's
    iterations; the phase reports it."""
    _patched_lstsq(monkeypatch, precision="mixed")
    monkeypatch.setattr(smoke, "GROUPS", (("clarkson_woodruff",),))
    failures = smoke.phase_kinds(8192, 96, 1e2, 0, compiled=False)
    assert any("not at full precision" in f for f in failures), failures


class _Compiled:
    def __init__(self, hlo, args, temp):
        self._hlo, self._mem = hlo, type("M", (), {
            "argument_size_in_bytes": args, "temp_size_in_bytes": temp,
        })()

    def as_text(self):
        return self._hlo

    def memory_analysis(self):
        return self._mem


def test_check_sharded_program_flags_a_moved_shard(smoke):
    m, n, chips = 4096, 64, 4
    shard_bytes = 4 * (m // chips) * n
    sketch = ("%all-reduce.1 = f32[256,64]{1,0} all-reduce(f32[256,64]{1,0} "
              "%p), channel_id=1, to_apply=%add\n")
    gather = ("%all-gather.2 = f32[4096,64]{1,0} all-gather(f32[1024,64]{1,0}"
              " %a), channel_id=2, dimensions={0}\n")
    ok = _Compiled(sketch, shard_bytes + 8, 10)
    assert smoke.check_sharded_program(ok, m, n, chips, 1e9) == []
    bad = smoke.check_sharded_program(
        _Compiled(sketch + gather, shard_bytes, 10), m, n, chips)
    assert len(bad) == 1 and "all-gather" in bad[0]
    replicated = smoke.check_sharded_program(
        _Compiled(sketch, 4 * shard_bytes, 10), m, n, chips)
    assert len(replicated) == 1 and "arguments" in replicated[0]
    too_big = smoke.check_sharded_program(
        _Compiled(sketch, shard_bytes, 10**9), m, n, chips, 1e9)
    assert len(too_big) == 1 and "exceed" in too_big[0]


def _run(args, cwd, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, *map(str, args)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_phase_sharded_tiny_on_four_cpu_devices():
    """The four-chip phase on four virtual CPU devices (a fresh process:
    the device count is fixed when JAX starts)."""
    code = (
        "import chip_smoke as s; s._import_repo(); "
        "f = s.phase_sharded(4096, 64, 1e4, 0, chips=4); "
        "assert f == [], f; print('ok')"
    )
    r = _run(["-c", code], ROOT,
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ok" in r.stdout


def test_refuses_without_tpu():
    r = _run([SCRIPT], ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_refuses_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    r = _run([lone], tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
