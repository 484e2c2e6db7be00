"""Public op: coalesced paged decode attention (jit'd wrapper).

``paged_attention`` = per-class Pallas passes + exact softmax-state merge.
``K_classes = ()`` gives the page-granular baseline (one DMA per page);
``K_classes = (k1, k2, ...)`` adds coalesced classes chosen by Algorithm 3
(``repro.kvcache.block_table.choose_kernel_classes``) from the allocator's
contiguity histogram.

Descriptor tables (window index + class assignment per 2^k window) are
host-side numpy (the serving scheduler computes them when block tables
change — the analogue of the OS filling aligned entries after a page walk).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kvcache.block_table import descriptor_tables, dma_descriptor_count
from .. import default_interpret
from .paged_attention import (merge_partials, paged_attention_class_pass,
                              to_pool_layout)


def build_descriptors(block_tables: np.ndarray, K_classes: Sequence[int]
                      ) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
    """Host-side: class-k window tables for the kernel (scheduler-time)."""
    return descriptor_tables(np.asarray(block_tables), K_classes)


@functools.partial(jax.jit, static_argnames=("page_size", "classes",
                                             "interpret"))
def _paged_attention_jit(q, k_pool, v_pool, kv_lens, desc_flat,
                         *, page_size: int, classes: Tuple[int, ...],
                         interpret: bool):
    """The class passes and their merge over pools already in the kernel's
    layout (:func:`~.paged_attention.kv_pool_shape`), as serving keeps them.
    """
    parts = []
    for i, k in enumerate(classes):
        win_idx, covered = desc_flat[2 * i], desc_flat[2 * i + 1]
        parts.append(paged_attention_class_pass(
            q, k_pool, v_pool, win_idx, covered, kv_lens,
            pages_per_block=1 << k, page_size=page_size,
            interpret=interpret))
    return merge_partials(parts).astype(q.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: np.ndarray, kv_lens: jax.Array,
                    *, page_size: int, K_classes: Sequence[int] = (),
                    interpret: Optional[bool] = None,
                    descriptors: Optional[Dict] = None) -> jax.Array:
    """q: [B, H, D] → [B, H, D] decode attention over the paged KV pool.

    Pools are ``[n_pages, T, KVH, D]``, the reference's layout; they are
    copied into the kernel's layout on each call.  ``interpret`` defaults
    to :func:`repro.kernels.default_interpret`."""
    if interpret is None:
        interpret = default_interpret()
    classes = tuple(sorted(set(list(K_classes) + [0]), reverse=True))
    if descriptors is None:
        descriptors = build_descriptors(block_tables, classes)
    desc_flat = []
    for k in classes:
        wi, cov = descriptors[k]
        desc_flat += [jnp.asarray(wi), jnp.asarray(cov)]
    return _paged_attention_jit(q, to_pool_layout(k_pool),
                                to_pool_layout(v_pool), jnp.asarray(kv_lens),
                                tuple(desc_flat), page_size=page_size,
                                classes=classes, interpret=interpret)


def dma_stats(block_tables: np.ndarray, K_classes: Sequence[int]
              ) -> Dict[str, float]:
    """Descriptor-count reduction (the paper's miss metric, TPU edition)."""
    return dma_descriptor_count(np.asarray(block_tables), K_classes)
