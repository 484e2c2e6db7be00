"""Operations and bytes the algorithms need, computed from shapes.

Kept with the benchmark so every PR counts the same work.  The
configuration's architecture module (``bench/arch/``) gives the weights a
token multiplies (``matmul_params``) and, for a token at a given context,
the keys each attention layer reads with that layer's head counts
(``attention_reads``); the counts here sum them.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench.arch import named


def matmul_params(config: dict) -> int:
    """Weights that every token multiplies (the embedding is a lookup)."""
    return named(config, "arch").matmul_params(config)


def attention_flops(config: dict, context: int) -> int:
    """Scores and weighted values of one query token at ``context``,
    summed over layers: 2 multiply-adds per head dimension for each key
    the layer reads."""
    return sum(4 * heads * D * keys for keys, heads, _, D in
               named(config, "arch").attention_reads(config, context))


def token_flops(config: dict, context: int) -> int:
    """Model FLOPs of one token that attends over ``context`` positions
    (itself included)."""
    return 2 * matmul_params(config) + attention_flops(config, context)


def prefill_flops(config: dict, length: int) -> int:
    """A prompt of ``length`` tokens, causal: token i is at context
    i + 1."""
    return (2 * matmul_params(config) * length
            + sum(attention_flops(config, n) for n in range(1, length + 1)))


def paged_attention_work(config: dict, contexts: Iterable[int],
                         kv_bytes: int = 2, q_bytes: int = 2
                         ) -> Tuple[int, int]:
    """(FLOPs, bytes) one decode step's paged attention needs: for every
    layer and every running request with ``n`` live tokens, read its query
    and the K and V of the keys the layer reads at context ``n``, write
    its output.  Pages the pool holds beyond those keys, and windows that
    hold none, are no part of the work."""
    reads = named(config, "arch").attention_reads
    flops = 0
    nbytes = 0
    for n in contexts:
        flops += attention_flops(config, n)
        nbytes += sum(2 * keys * kv_heads * D * kv_bytes
                      + 2 * heads * D * q_bytes
                      for keys, heads, kv_heads, D in reads(config, n))
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the longer of compute at peak and traffic at peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
