"""Pallas TPU kernels (validated with interpret=True on CPU vs ref.py oracles).

* ``paged_attention`` — the paper's technique as a kernel: per-class
  coalesced superblock DMA over the paged KV pool (ops.paged_attention).
* ``flash_attention`` — tiled causal online-softmax forward for
  prefill/serving (ops.flash_attention_gqa).
* ``tlb_sweep`` — the sweep engine's Pallas backend: one lane per grid
  row, all TLB state resident in scratch for the whole trace
  (ops.run_lanes_pallas; select with ``run_sweep(backend='pallas')``).
"""
import jax


def default_interpret() -> bool:
    """The one rule for a kernel's ``interpret`` knob when the caller leaves
    it ``None``: compiled on a TPU, the Pallas interpreter everywhere else
    (no other backend can compile a Mosaic kernel)."""
    return jax.default_backend() != "tpu"
