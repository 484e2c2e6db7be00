"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts (block tiling, VMEM
budgets), so the kernels and the XLA lane program of the main paths are
compiled here for one chip of a ``v5e:2x2`` topology: the paged-attention
kernel at internlm2-1.8b's widths (and the reduced ones) and the sweep's
lane program at a smoke-tier shape.  Nothing runs; a pass means the chip's
compiler accepts the program.  The topology is described inside a fixture
(only one process at a time may load the TPU library), and every test here
skips when it cannot be described.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

# (B, H, KVH, D, page size, pool pages, logical pages per row):
# internlm2-1.8b's published widths as the chip smoke serves them, and the
# reduced internlm2 the CPU benchmarks serve (head dim 32 < one lane tile)
WIDTHS = {"internlm2-1.8b": (8, 16, 8, 128, 16, 1024, 64),
          "internlm2-1.8b-reduced": (4, 4, 2, 32, 8, 256, 16)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("classes", [(0,), (2, 0)])
@pytest.mark.parametrize("widths", list(WIDTHS))
def test_paged_attention_compiles_for_v5e(one_chip, widths, classes):
    from repro.kernels.paged_attention.ops import _paged_attention_jit
    from repro.kernels.paged_attention.paged_attention import kv_pool_shape

    B, H, KVH, D, page, n_pages, max_pages = WIDTHS[widths]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds(kv_pool_shape(n_pages, page, KVH, D), jnp.bfloat16)
    desc = []
    for k in classes:
        desc += [sds((B, max_pages >> k), jnp.int32),
                 sds((B, max_pages >> k), jnp.int8)]
    compiled = _paged_attention_jit.lower(
        sds((B, H, D), jnp.bfloat16), pool, pool, sds((B,), jnp.int32),
        tuple(desc), page_size=page, classes=classes,
        interpret=False).compile()
    assert compiled.as_text().count("tpu_custom_call") >= len(classes)


def test_xla_lane_program_compiles_for_v5e(one_chip):
    from repro.core import base_spec, colt_spec, kaligned_for_mapping
    from repro.core.lane_program import (init_batched_state,
                                         needs_switch_pass, pack_lanes)
    from repro.core.sweep import DEFAULT_BLOCK, SweepCell, _run_lanes_jit
    from repro.scenarios import get_scenario

    d = get_scenario("paper-mcf").materialize(n_pages=1 << 15,
                                              trace_len=4096, trace_seed=3)
    cells = [SweepCell(s, d.mapping, d.trace)
             for s in (base_spec(), colt_spec(),
                       kaligned_for_mapping(d.mapping, psi=2))]
    lanes, stacks, (L, sets, ways), seg_bounds = pack_lanes(
        cells, device_count=1)
    st0 = init_batched_state(L, sets, ways, lanes["pred0"], lanes["asid0"],
                             with_ctlb=False, with_dp=False)
    compiled = _run_lanes_jit.lower(
        _abstract(lanes, one_chip), _abstract(stacks, one_chip),
        _abstract(st0, one_chip), seg_bounds, DEFAULT_BLOCK,
        needs_switch_pass(lanes)).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
