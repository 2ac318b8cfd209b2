"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered for a described (not attached) v5e
chip and compiled by the chip's own compiler, which refuses what interpret
mode cannot see — VMEM overflows, casts and layouts Mosaic does not
support, programs that do not fit HBM.  The topology is described inside a
fixture, in this process, so no worker loads the TPU library while
collecting.
"""
import importlib.util
import os
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import (
    countsketch_apply,
    fused_gaussian_sketch,
    hadamard_transform,
    residual_pass,
    sketch_matmul,
)
from repro.core import backend as backend_lib
from repro.core import iterative_sketching, sketched_lstsq
from repro.kernels.tsqr import countsketch_gram, gaussian_gram
from repro.sharding import make_mesh

# The paper's Fig. 3 width (m = 2^20, n = 1000) for the CountSketch main
# path and SRHT; the dense kinds at the m = 2^17 the smoke test runs them.
# The four-chip phase: A of 2^22 x 1000 f32 row-sharded over the 2x2 host.
M_FULL, M_DENSE, M_SHARDED, N, D = 1 << 20, 1 << 17, 1 << 22, 1000, 4000
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def v5e():
    """A described v5e host: four chips in a 2x2 mesh."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _footprint(compiled) -> tuple[int, int]:
    ma = compiled.memory_analysis()
    return ma.argument_size_in_bytes, ma.temp_size_in_bytes


@pytest.mark.parametrize("kernel", ["apply", "gram"])
def test_countsketch_compiles_at_fig3_width(one_chip, kernel):
    """CountSketch at 2^20 x 1000 f32 fits one chip with A not copied
    more than once (the padded copy of A used to add 8.6 GB)."""
    A = _spec(one_chip, (M_FULL, N), jnp.float32)
    h = _spec(one_chip, (M_FULL,), jnp.int32)
    s = _spec(one_chip, (M_FULL,), jnp.float32)
    fn = countsketch_apply if kernel == "apply" else countsketch_gram
    compiled = _compile(lambda A, h, s: fn(A, h, s, D, interpret=False), A, h, s)
    args, temp = _footprint(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    assert temp <= 1.05 * args, (args, temp)
    assert args + temp < HBM_BYTES


@pytest.mark.parametrize("shape,dtype", [
    ((M_FULL, N), jnp.float32),  # A of fig3.fresh, stored by columns
    ((M_FULL,), jnp.float32),  # its b
    ((M_FULL, N), jnp.bfloat16),  # precision="mixed"
    ((M_FULL, 1024), jnp.float32),  # stored by rows
])
def test_countsketch_reads_a_where_it_lies(one_chip, shape, dtype):
    """The apply reads A in the order XLA stores it: no relayout or padded
    copy of A, only the resident output in VMEM."""
    A = _spec(one_chip, shape, dtype)
    h = _spec(one_chip, (M_FULL,), jnp.int32)
    s = _spec(one_chip, (M_FULL,), dtype)
    compiled = _compile(
        lambda A, h, s: countsketch_apply(A, h, s, D, interpret=False), A, h, s)
    args, temp = _footprint(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    assert temp <= 0.01 * args, (args, temp)


@pytest.mark.parametrize("shape", [(M_FULL, N), (M_FULL, 1024)])
def test_residual_pass_reads_a_where_it_lies(one_chip, shape):
    """The one-pass residual kernel at fig3 width (A stored by columns)
    and at a width stored by rows: no relayout copy of A, whose 4.2 GB
    would show in the temporaries."""
    m, n = shape
    A, x, b = (_spec(one_chip, s, jnp.float32) for s in (shape, (n,), (m,)))
    compiled = _compile(
        lambda A, x, b: residual_pass(A, x, b, interpret=False), A, x, b)
    args, temp = _footprint(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    assert temp <= 0.01 * args, (args, temp)


def _computations(text: str) -> dict[str, list[str]]:
    """The instructions of each computation of compiled HLO text."""
    comps, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[name.lstrip("%")] = []
        elif name is not None:
            comps[name.lstrip("%")].append(line.strip())
    return comps


def test_refinement_reads_a_once_per_iteration_at_fig3_width(one_chip):
    """iterative_sketching at 2^20 x 1000 f32, compiled with the Pallas
    kernels: the residual_pass kernel is in the body of the refinement's
    while loop, and once outside it for the returned iterate, and the
    program fits one chip with no copy of A."""
    A = _spec(one_chip, (M_FULL, N), jnp.float32)
    b = _spec(one_chip, (M_FULL,), jnp.float32)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend_lib, "default_interpret", lambda platform=None: False)
        compiled = _compile(
            partial(iterative_sketching, backend="pallas"), A, b, key)
    comps = _computations(compiled.as_text())
    bodies = {line.split("body=%")[1].split(",")[0]
              for lines in comps.values() for line in lines
              if " while(" in line}
    homes = [name for name, lines in comps.items() for line in lines
             if line.startswith("%residual_pass")
             and 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(h in bodies for h in homes) == [False, True], homes
    args, temp = _footprint(compiled)
    assert temp <= 0.01 * args, (args, temp)
    assert args + temp < HBM_BYTES


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hadamard_compiles_at_fig3_m(one_chip, dtype):
    """SRHT's transform at m = 2^20 fits the 16 MiB scoped VMEM, and
    half-precision inputs accumulate in f32 (a bf16 MXU accumulator is
    refused by Mosaic)."""
    x = _spec(one_chip, (M_FULL, 256), dtype)
    compiled = _compile(lambda x: hadamard_transform(x, interpret=False), x)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["fused_gaussian_sketch", "gaussian_gram"])
def test_gaussian_kernels_compile(one_chip, kernel):
    """The in-kernel threefry + Box–Muller stream lowers under Mosaic."""
    A = _spec(one_chip, (M_DENSE, N), jnp.float32)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    fn = fused_gaussian_sketch if kernel == "fused_gaussian_sketch" else gaussian_gram
    compiled = _compile(lambda A, k: fn(A, k, D, interpret=False), A, key)
    assert "tpu_custom_call" in compiled.as_text()


def test_sketch_matmul_compiles(one_chip):
    S = _spec(one_chip, (D, M_DENSE), jnp.float32)
    A = _spec(one_chip, (M_DENSE, N), jnp.float32)
    compiled = _compile(lambda S, A: sketch_matmul(S, A, interpret=False), S, A)
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_solve_compiles_on_four_chips(v5e):
    """``sketched_lstsq`` with A (2^22 x 1000 f32) row-sharded over the
    2x2 host, compiled with the Pallas kernels and the chip's tiles as the
    four-chip smoke phase runs it: no collective moves a shard of A, each
    chip's arguments hold its quarter, and each chip's program fits HBM."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parent.parent / "chip_smoke.py"
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    mesh = make_mesh((4,), ("data",), devices=v5e.devices)
    A = _spec(NamedSharding(mesh, P("data", None)), (M_SHARDED, N), jnp.float32)
    b = _spec(NamedSharding(mesh, P("data")), (M_SHARDED,), jnp.float32)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))
    blocks = backend_lib.kernel_blocks
    with pytest.MonkeyPatch.context() as mp:
        # compiled kernels, tiled for the described chip, from a CPU host
        mp.setattr(backend_lib, "default_interpret", lambda platform=None: False)
        mp.setattr(backend_lib, "kernel_blocks",
                   lambda *a, **k: blocks(*a, **{**k, "interpret": True}))
        compiled = _compile(
            partial(sketched_lstsq, mesh=mesh, backend="pallas"), A, b, key
        )
    assert "tpu_custom_call" in compiled.as_text()
    assert smoke.check_sharded_program(compiled, M_SHARDED, N, 4, HBM_BYTES) == []


def test_lstsq_on_a_sharded_a_compiles_on_four_chips(v5e):
    """``lstsq`` given A (2^22 x 1000 f32) row-sharded over the 2x2 host
    takes the mesh path, and the program it runs there passes the checks
    of the twin test above.  lstsq routes on the arrays' shardings, which
    described shapes carry too; the call it makes to ``sketched_lstsq`` is
    compiled here in place of being run."""
    from repro.core import distributed, lstsq

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parent.parent / "chip_smoke.py"
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    mesh = make_mesh((4,), ("data",), devices=v5e.devices)
    A = _spec(NamedSharding(mesh, P("data", None)), (M_SHARDED, N), jnp.float32)
    b = _spec(NamedSharding(mesh, P("data")), (M_SHARDED,), jnp.float32)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))
    calls, solve = [], distributed.sketched_lstsq

    def compile_instead(A, b, key, **kw):
        calls.append(kw)
        calls.append(_compile(partial(solve, **kw), A, b, key))
        zero = jnp.zeros((), jnp.int32)
        return distributed.SolveResult(
            x=jnp.zeros(N), istop=zero, itn=zero, rnorm=zero, arnorm=zero,
            used_fallback=jnp.asarray(False), method="saa")

    blocks = backend_lib.kernel_blocks
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend_lib, "default_interpret", lambda platform=None: False)
        mp.setattr(backend_lib, "kernel_blocks",
                   lambda *a, **k: blocks(*a, **{**k, "interpret": True}))
        mp.setattr(distributed, "sketched_lstsq", compile_instead)
        res = lstsq(A, b, key, backend="pallas")
    assert res.method == "saa"
    kw, compiled = calls
    assert kw["mesh"] is mesh and kw["axes"] == ("data",)
    assert "tpu_custom_call" in compiled.as_text()
    assert smoke.check_sharded_program(compiled, M_SHARDED, N, 4, HBM_BYTES) == []
