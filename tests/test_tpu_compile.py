"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned or dynamic-lane slices, layouts it cannot
relayout, packed-dtype row loads, VMEM over the scoped limit.  These tests
compile each kernel family for a described (not attached) v5e chip at the
sizes ``chip_smoke.py`` runs, with the blocks the tuner picks there, and
check that the compiled program holds the Pallas kernel.  Nothing runs.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import common, engine, ops
from repro.kernels.fused_cn import fused_cn_tridiag_pallas
from repro.kernels.fused_cn_penta import fused_cn_penta_pallas
from repro.core import recurrence
from repro.pde.stencil import apply_periodic_stencil
from repro.solver import BandedSystem
from repro.solver import pallas as solver_pallas

#: The smoke's sizes: the paper's storage table, and large N.
TABLE_N, TABLE_M = 1024, 65536
LARGE_N, LARGE_M = 16384, 8192
#: The CN stencil's grid, at a batch small enough to compile in a second.
STENCIL_N, STENCIL_M = 1024, 4096
#: mamba2-130m's SSD inter-chunk scan at batch 8 x seq 2048.
SSD_N, SSD_M = 2048 // 64, 8 * 24 * 64 * 128
#: The benchmark's CN cells: N=1024, M=524288.
CN_N, CN_M = 1024, 524288


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        return text.count('custom_call_target="tpu_custom_call"')
    return run


def _system(bandwidth, mode, n, m):
    batch = m if mode == "batch" else None
    if bandwidth == 3:
        return BandedSystem.tridiag(-0.4, 1.8, -0.4, n=n, mode=mode,
                                    batch=batch)
    return BandedSystem.penta(0.1, -0.4, 1.8, -0.4, 0.1, n=n, mode=mode,
                              batch=batch)


def _tuned(bandwidth, mode, n, m, fused):
    """The tuner's (block_m, block_n) at this size; ``fused=False`` at a
    streamed size asks for the two-call pair's blocks."""
    system = _system(bandwidth, mode, n, m)
    bm, bn = solver_pallas.auto_tune(system)
    if bn is not None and fused != solver_pallas.resolve_fused(system, bm,
                                                               bn):
        bm = next(b for b in solver_pallas._lane_tiles(system)
                  if solver_pallas.auto_block_n(system, b))
        bn = solver_pallas.auto_block_n(system, bm)
    return bm, bn


def _compile_spec(compile_for_chip, name, n, m, *, storage=jnp.float32,
                  fused=False):
    spec = engine.REGISTRY[name]
    bm, bn = _tuned(spec.bandwidth, spec.mode, n, m, fused)
    assert (bn is not None) == spec.streamed
    kw = dict(block_m=bm, interpret=False)
    if spec.streamed:
        kw["block_n"] = bn
    if spec.layout == "shared":
        solver = engine.shared_solver(spec)
        shapes = [((spec.lhs_rows, n), jnp.float32), ((n, m), storage)]
        if spec.uniform:
            shapes.append(((1, 1), jnp.float32))
            fn = lambda lhs, rhs, eps: solver(lhs, rhs, eps=eps, **kw)
        else:
            fn = lambda lhs, rhs: solver(lhs, rhs, **kw)
    else:
        solver = engine.batch_solver(spec)
        shapes = [((n, m), storage)] * (spec.bandwidth + 1)
        fn = lambda *a: solver(*a, **kw)
    return compile_for_chip(fn, *shapes), spec.num_pallas_calls


@pytest.mark.parametrize("name,n,m,fused", [
    ("thomas_constant", TABLE_N, TABLE_M, False),
    ("penta_uniform", TABLE_N, TABLE_M, False),
    ("thomas_constant_t", TABLE_N, TABLE_M, False),
    ("thomas_batch", TABLE_N, TABLE_M, False),
    ("penta_batch", TABLE_N, TABLE_M, False),
    ("thomas_constant_streamed", LARGE_N, LARGE_M, False),
    ("penta_constant_streamed_fused", LARGE_N, LARGE_M, True),
    ("penta_constant_streamed_fused_t", LARGE_N, LARGE_M, True),
    ("thomas_batch_streamed", LARGE_N, LARGE_M, False),
    ("thomas_batch_streamed_fused", LARGE_N // 2, LARGE_M, True),
])
def test_sweep_kernel_compiles_for_v5e(compile_for_chip, name, n, m, fused):
    calls, want = _compile_spec(compile_for_chip, name, n, m, fused=fused)
    assert calls == want


def _scoped_vmem(text: str, key: str) -> int:
    """Bytes of the Pallas kernel's ``key`` scoped-memory config in the
    compiled HLO: ``scoped_memory_configs`` is the limit it requested,
    ``used_scoped_memory_configs`` what Mosaic allocated."""
    (call,) = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    (size,) = re.findall(rf'"{key}":\[\{{[^}}]*"size":"(\d+)"', call)
    return int(size)


@pytest.mark.parametrize("row", ["thomas_constant", "penta_constant"])
def test_packed_sweep_compiles_for_v5e_within_its_vmem_limit(
        one_chip, no_persistent_cache, row):
    """The CN cells' solve dispatches the packed sweep; compiled for a v5e
    it allocates no more VMEM than its fit model counts, and no more than
    the limit it requests."""
    spec, block_m = ops._packed_choice(engine.REGISTRY[row], CN_N, CN_M,
                                       jnp.float32, None)
    assert spec.name == f"{row}_packed"
    solver = engine.shared_solver(spec)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((spec.lhs_rows, CN_N), (CN_N, CN_M))]
    text = jax.jit(lambda lhs, rhs: solver(
        lhs, rhs, block_m=block_m, interpret=False)).lower(
            *args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    used = _scoped_vmem(text, "used_scoped_memory_configs")
    limit = _scoped_vmem(text, "scoped_memory_configs")
    assert limit == common.vmem_limit(spec)
    assert used <= common.spec_working_set(spec, CN_N, block_m)[0] <= limit


@pytest.mark.parametrize("name,n,m,fused", [
    ("thomas_constant", TABLE_N, TABLE_M, False),
    ("thomas_constant_streamed_fused", LARGE_N, LARGE_M, True),
])
def test_bf16_storage_compiles_for_v5e(compile_for_chip, name, n, m, fused):
    calls, want = _compile_spec(compile_for_chip, name, n, m,
                                storage=jnp.bfloat16, fused=fused)
    assert calls == want


@pytest.mark.parametrize("name,n,m", [
    ("recur1", SSD_N, SSD_M),
    ("recur2", SSD_N, SSD_M),
    ("recur2_streamed_rev", LARGE_N, LARGE_M),
])
def test_recurrence_kernel_compiles_for_v5e(compile_for_chip, name, n, m):
    spec = engine.REGISTRY[name]
    bm, bn = recurrence._auto_blocks(spec.order, n, m, 4)
    assert (bn is not None) == spec.streamed
    kw = dict(block_m=bm, interpret=False)
    if spec.streamed:
        kw["block_n"] = bn
    solver = engine.recurrence_solver(spec)
    calls = compile_for_chip(lambda *a: solver(*a, **kw),
                             *[((n, m), jnp.float32)] * (spec.order + 1))
    assert calls == 1


def test_fused_cn_steps_compile_for_v5e(compile_for_chip):
    n, m = TABLE_N, TABLE_M
    tri = compile_for_chip(
        lambda lhs, z, p, c: fused_cn_tridiag_pallas(lhs, z, p, c,
                                                     interpret=False),
        ((3, n), jnp.float32), ((n, 1), jnp.float32),
        ((1, 8), jnp.float32), ((n, m), jnp.float32))
    penta = compile_for_chip(
        lambda lhs, z, mi, p, c: fused_cn_penta_pallas(lhs, z, mi, p, c,
                                                       interpret=False),
        ((5, n), jnp.float32), ((n, 4), jnp.float32), ((4, 4), jnp.float32),
        ((1, 16), jnp.float32), ((n, m), jnp.float32))
    assert (tri, penta) == (1, 1)


@pytest.mark.parametrize("weights", [
    [0.3, 0.4, 0.3],
    [-0.2, 0.8, -0.2, 0.8, -0.2],
])
def test_periodic_stencil_is_one_pass_on_v5e(one_chip, no_persistent_cache,
                                             weights):
    """The CN right-hand side reads the field once and writes it once: the
    compiler's bytes stay near two field sizes for the 3- and 5-point
    stencils (at this size a sum of ``jnp.roll``s moved 12 and 17)."""
    field = jax.ShapeDtypeStruct((STENCIL_N, STENCIL_M), jnp.float32,
                                 sharding=one_chip)
    compiled = jax.jit(lambda f: apply_periodic_stencil(f, weights)) \
        .lower(field).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    fields = cost["bytes accessed"] / (STENCIL_N * STENCIL_M * 4)
    assert fields <= 2.1
