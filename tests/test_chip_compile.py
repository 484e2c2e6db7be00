"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts (block tiling, VMEM
budgets), so the kernels and the XLA lane program of the main paths are
compiled here for one chip of a ``v5e:2x2`` topology: the paged-attention
kernel at internlm2-1.8b's widths (and the reduced ones) and the sweep's
lane program at a smoke-tier shape and at the paper's L2 geometry, where
its scan bodies are read for scatters, branches and padded copies of the
state.  Nothing runs; a pass means the chip's compiler accepts the
program.  The topology is described inside a fixture
(only one process at a time may load the TPU library), and every test here
skips when it cannot be described.
"""
import re

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


def _hlo_computations(text):
    """Optimised HLO text -> {computation name: [instruction lines]}."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[cur.lstrip("%")] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip():
            comps[cur.lstrip("%")].append(line.strip())
    return comps


def _reachable(comps, root):
    """``root`` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            todo.extend(n for n in re.findall(r"%([\w.\-]+)", line)
                        if n in comps)
    return seen


def _tiled_bytes(dims, layout, itemsize=4):
    """Bytes of an array of ``dims`` in a ``{minor_to_major:T(a,b)}``
    layout: the tile pads the most minor dimensions up to its multiples."""
    m = re.match(r"\{([\d,]*)(?::([^}]*))?\}", layout)
    minor_to_major = [int(d) for d in m.group(1).split(",")]
    padded = list(dims)
    tile = re.search(r"T\(([\d,]+)\)", m.group(2) or "")
    if tile:
        for j, t in enumerate(reversed(tile.group(1).split(","))):
            ax = minor_to_major[j]
            padded[ax] = -(-padded[ax] // int(t)) * int(t)
    return int(np.prod(padded)) * itemsize


def test_lane_program_state_stays_unpadded_on_v5e(one_chip):
    """At the paper's L2 (128 sets x 8 ways) and a 64-lane batch, the scan
    bodies the chip's compiler emits neither scatter nor branch, and the
    per-step body writes no state plane in a layout padded beyond twice
    its bytes: the state-access form for a TPU keeps the planes in one
    unpadded layout instead of copying them between padded ones."""
    from repro.core import (base_spec, cluster_spec, colt_spec,
                            kaligned_for_mapping, rmm_spec, thp_spec)
    from repro.core.lane_program import (init_batched_state,
                                         needs_switch_pass, pack_lanes)
    from repro.core.sweep import DEFAULT_BLOCK, SweepCell, _run_lanes_jit
    from repro.scenarios import get_scenario

    d = get_scenario("paper-mcf").materialize(n_pages=1 << 12,
                                              trace_len=1024, trace_seed=3)
    specs = [base_spec(), thp_spec(), colt_spec(), rmm_spec(),
             cluster_spec(), kaligned_for_mapping(d.mapping, psi=2)]
    cells = [SweepCell(specs[i % len(specs)], d.mapping, d.trace)
             for i in range(64)]
    lanes, stacks, (L, sets, ways), seg_bounds = pack_lanes(
        cells, device_count=1)
    assert (L, sets, ways) == (64, 128, 8)
    st0 = init_batched_state(L, sets, ways, lanes["pred0"], lanes["asid0"])
    text = _run_lanes_jit.lower(
        _abstract(lanes, one_chip), _abstract(stacks, one_chip),
        _abstract(st0, one_chip), seg_bounds, DEFAULT_BLOCK,
        needs_switch_pass(lanes)).compile().as_text()

    comps = _hlo_computations(text)
    loops = [(c, re.search(r"body=%([\w.\-]+)", line).group(1))
             for c, lines in comps.items() for line in lines
             if " while(" in line]
    bodies = {body for _, body in loops}
    # the block scan's body holds the step scan's loop
    step_bodies = {body for c, body in loops if c in bodies}
    assert bodies and step_bodies
    for body in bodies:
        for c in _reachable(comps, body):
            for line in comps[c]:
                assert " scatter(" not in line, (c, line[:160])
                assert " conditional(" not in line, (c, line[:160])

    # the TLB structures' planes, [L, sets, ways, fields] or [L, n, fields]
    planes = {tuple(sorted(a.shape)): k for k, a in st0.items()
              if a.ndim > 2}
    written = set()
    for body in step_bodies:
        for line in comps[body]:
            op = re.match(r"(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z\-]+)\(", line)
            if op is None or op.group(2) not in ("copy", "fusion"):
                continue
            for dims, layout in re.findall(r"s32\[([\d,]+)\](\{[^}]*\})",
                                           op.group(1)):
                dims = [int(x) for x in dims.split(",")]
                plane = planes.get(tuple(sorted(dims)))
                if plane is None:
                    continue
                written.add(plane)
                padded = _tiled_bytes(dims, layout)
                assert padded <= 2 * 4 * np.prod(dims), (
                    plane, op.group(2), dims, layout, padded)
    assert "l2" in written          # the check found the L2 plane
