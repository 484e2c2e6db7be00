"""Coalesced paged decode attention — Pallas TPU kernel (the paper on TPU).

One kernel, parameterized by ``pages_per_block = 2^k`` (the class).  The
baseline paged attention is the class-0 instance (one DMA per page, vLLM
style); the coalesced scheme runs one instance per k ∈ K over the windows
*assigned* to that class (Algorithm 1's rightward-compatible fill, computed
host-side in ``repro.kvcache.block_table``), then merges the per-class
partial softmax states exactly.

Why this is the paper's mechanism and not just inspiration:

* class-k window ↔ k-bit aligned PTE whose contiguity spans its window;
* the BlockSpec ``index_map`` consulting the scalar-prefetched window table
  ↔ the aligned TLB lookup (translation happens per 2^k pages, not per page);
* one grid step loads 2^k·page_size tokens of every KV head in ONE
  contiguous DMA per pool (K, V) ↔ one TLB entry covering 2^k pages
  (translation-overhead reduction = DMA-descriptor reduction);
* uncovered windows fall to the class-0 pass ↔ regular entries.

The pool layout (one owner: :func:`kv_pool_shape` / :func:`to_pool_layout`)
is ``[n_pages, T, KVH * Dp]``: the KV heads side by side on the minor axis,
each head's dim zero-padded to whole 128-lane tiles (``Dp``).  A class-k
window is then the contiguous ``[2^k·T, KVH·Dp]`` slab that one block
fetches, and grouping pages into windows only reshapes leading dims.
Zero lanes add nothing to q·k, and the padded lanes of p·v are sliced off.

VMEM budget: a class-k block is (2^k·T, KVH·Dp) for K and for V → e.g.
k=6, T=16, KVH=8, Dp=128 in bfloat16 ⇒ 2·1024·1024·2B = 4MB per step, 8MB
double-buffered; ``choose_kernel_classes`` caps k at 6 and the pass raises
the scoped-VMEM limit to what its blocks need.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import default_interpret

NEG_INF = -1e30
LANES = 128
_MiB = 1 << 20


def pool_head_dim(head_dim: int) -> int:
    """A head's width in the pool: ``head_dim`` rounded up to whole lanes."""
    return -(-head_dim // LANES) * LANES


def kv_pool_shape(n_pages: int, page_size: int, n_kv_heads: int,
                  head_dim: int) -> Tuple[int, int, int]:
    """Shape of one K or V pool: ``[n_pages, T, KVH * Dp]``."""
    return (n_pages, page_size, n_kv_heads * pool_head_dim(head_dim))


def to_pool_layout(kv: jax.Array) -> jax.Array:
    """``[..., KVH, D]`` → ``[..., KVH * Dp]``, the head dim zero-padded."""
    *lead, kvh, d = kv.shape
    pad = [(0, 0)] * (kv.ndim - 1) + [(0, pool_head_dim(d) - d)]
    return jnp.pad(kv, pad).reshape(*lead, kvh * pool_head_dim(d))


def _class_kernel(win_idx_ref, cov_ref, len_ref,   # scalar prefetch
                  q_ref, k_ref, v_ref,             # VMEM blocks
                  o_ref, m_ref, l_ref,             # outputs (revisited)
                  *, tokens_per_win: int, n_kv_heads: int, head_dim: int,
                  scale: float):
    """One (row b, window j) step: the window's K and V for every KV head
    arrive as one block each.  Head h reads lanes [h·Dp, (h+1)·Dp) of
    them, a static lane-aligned slice, so every dot is 2-D and no reshape
    stays in the kernel (Mosaic refuses those)."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(cov_ref[b, j] == 1)
    def _attend():
        W, Dp = tokens_per_win, head_dim
        pos = j * W + jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        live = pos < len_ref[b]
        for h in range(n_kv_heads):
            lanes = slice(h * Dp, (h + 1) * Dp)
            q = q_ref[0, h].astype(jnp.float32)              # [G, Dp]
            k = k_ref[0, :, lanes].astype(jnp.float32)       # [W, Dp]
            v = v_ref[0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [G, W]
            s = jnp.where(live, s, NEG_INF)

            m_prev = m_ref[0, h]                             # [G, 1]
            l_prev = l_ref[0, h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            pv = jax.lax.dot_general(
                p, v, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G, Dp]
            o_ref[0, h] = o_ref[0, h] * alpha + pv
            m_ref[0, h] = m_new
            l_ref[0, h] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)


def paged_attention_class_pass(
        q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
        win_idx: jax.Array, covered: jax.Array, kv_lens: jax.Array,
        *, pages_per_block: int, page_size: int,
        scale: Optional[float] = None, interpret: Optional[bool] = None
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One class-k pass.

    q: [B, H, D]; pools: :func:`kv_pool_shape`; win_idx/covered: [B, n_win]
    (physical window index / class-assignment mask); kv_lens: [B].
    Returns unnormalized (o [B,H,D] f32, m [B,H] f32, l [B,H] f32).
    ``interpret`` defaults to :func:`repro.kernels.default_interpret`.
    """
    if interpret is None:
        interpret = default_interpret()
    B, H, D = q.shape
    n_pages, T, C = k_pool.shape
    Dp = pool_head_dim(D)
    KVH = C // Dp
    G = H // KVH
    P2 = pages_per_block
    assert T == page_size
    assert C == KVH * Dp and H == KVH * G, (C, KVH, Dp, H)
    assert n_pages % P2 == 0, (n_pages, P2)
    W = P2 * T
    n_win = win_idx.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)

    # a window is one [W, KVH*Dp] slab: grouping pages only merges leading
    # dims, so this reshape moves no data
    kp = k_pool.reshape(n_pages // P2, W, C)
    vp = v_pool.reshape(n_pages // P2, W, C)
    qg = jnp.pad(q, ((0, 0), (0, 0), (0, Dp - D))).reshape(B, KVH, G, Dp)

    kernel = functools.partial(_class_kernel, tokens_per_win=W,
                               n_kv_heads=KVH, head_dim=Dp, scale=scale)
    out_shapes = (
        jax.ShapeDtypeStruct((B, KVH, G, Dp), jnp.float32),
        jax.ShapeDtypeStruct((B, KVH, G, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, KVH, G, 1), jnp.float32),
    )
    row_block = lambda b, j, *s: (b, 0, 0, 0)   # noqa: E731
    kv_block = pl.BlockSpec((1, W, C),
                            lambda b, j, win, cov, ln: (win[b, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_win),
        in_specs=[pl.BlockSpec((1, KVH, G, Dp), row_block), kv_block,
                  kv_block],
        out_specs=[pl.BlockSpec((1, KVH, G, Dp), row_block),
                   pl.BlockSpec((1, KVH, G, 1), row_block),
                   pl.BlockSpec((1, KVH, G, 1), row_block)],
    )
    # K and V blocks, double-buffered, beside the f32 upcasts of one head
    kv_bytes = 2 * 2 * W * C * k_pool.dtype.itemsize
    vmem = kv_bytes + 4 * W * Dp * 4 + 8 * _MiB
    o, m, l = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem, 32 * _MiB)),
        interpret=interpret,
    )(win_idx.astype(jnp.int32), covered.astype(jnp.int32),
      kv_lens.astype(jnp.int32), qg, kp, vp)
    return o[..., :D].reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)


def merge_partials(parts) -> jax.Array:
    """Exact merge of per-class (o_unnorm, m, l) partial-softmax states."""
    ms = jnp.stack([p[1] for p in parts])            # [C, B, H]
    m_star = jnp.max(ms, axis=0)
    o = 0.0
    lsum = 0.0
    for o_k, m_k, l_k in parts:
        w = jnp.exp(m_k - m_star)
        o = o + o_k * w[..., None]
        lsum = lsum + l_k * w
    return o / jnp.maximum(lsum, 1e-30)[..., None]
