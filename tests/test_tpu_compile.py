"""Compiles for a DESCRIBED TPU v5e (v5e:2x2), no chip attached: the
main path's kernels at real widths, through the TPU compiler that ships
with jaxlib.  What the chip's compiler refuses — a block shape off the
(8, 128) tiling, more VMEM than a core has, a program over 16 GB —
fails here at no chip time (on-chip-measurement guide, section 2).

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and every xdist worker imports this
file."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mpisppy_tpu.models import farmer
from mpisppy_tpu.ops.pallas_pdhg import VMEM_BUDGET, fused_chunk
from mpisppy_tpu.ops.pdhg import PDHGSolver, prepare_split_native

HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _chunk_args(sharding, S, M, N, dtype=jnp.float32):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (sds(S, M, N), sds(S, N), sds(S, N), sds(S, N), sds(S, N),
            sds(S, M), sds(S, M), sds(S, N), sds(S, M), sds(S), sds(S))


@pytest.mark.parametrize("S", [64, 12])
def test_fused_chunk_compiles_at_farmer_width(one_chip, S):
    """farmer crops_multiplier=10 per-scenario shape (61 x 120); S=12
    is not a multiple of 8 and used to pick an illegal tile of 4."""
    c = fused_chunk.lower(*_chunk_args(one_chip, S, 61, 120),
                          n_steps=40, tile_s=8).compile()
    assert "tpu_custom_call" in c.as_text()


def test_fused_chunk_large_scenario_fits_or_refuses(one_chip):
    """Large per-scenario matrices: either it compiles, or the kernel
    refuses up front — never a 185 MB request to Mosaic."""
    try:
        c = fused_chunk.lower(*_chunk_args(one_chip, 64, 1200, 1500),
                              n_steps=40, tile_s=8).compile()
    except ValueError as e:
        assert "VMEM" in str(e)
        assert f"{VMEM_BUDGET / 2**20:.0f} MiB" in str(e)
    else:
        assert "tpu_custom_call" in c.as_text()


def test_pdhg_solve_compiles_at_reference_size(one_chip):
    """The jitted PDHG solve at the reference instance, farmer S=1000 x
    crops_multiplier=1000 split-native in f32 (6001 x 12000 per
    scenario), fits one chip's HBM."""
    b = farmer.build_batch(1000, crops_multiplier=1000, dtype=np.float32,
                           split=True)

    def sds(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    A = jax.tree.map(sds, b.A)
    prep = jax.eval_shape(prepare_split_native, A, sds(b.row_lo),
                          sds(b.row_hi))
    prep = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), prep)
    solver = PDHGSolver(max_iters=1000, eps=1e-5)
    vec = [sds(v) for v in (b.c, b.qdiag, b.lb, b.ub, b.obj_const)]
    x0, y0 = sds(b.c), sds(b.row_lo)
    eps = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    c = jax.jit(solver._solve_impl).lower(
        prep, *vec, x0, y0, None, eps).compile()
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
