"""Operations and bytes the algorithms need, computed from shapes.

Kept with the benchmark so every PR counts the same work.  A dense
decoder's configuration file gives the sizes (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``vocab_size``).
"""
from __future__ import annotations

from typing import Iterable, Tuple


def matmul_params(config: dict) -> int:
    """Weights that every token multiplies: each layer's projections and
    feed-forward, and the output head (the embedding is a lookup)."""
    d = config["hidden_size"]
    D = config["head_dim"]
    q = config["num_attention_heads"] * D
    kv = config["num_key_value_heads"] * D
    layer = d * q + 2 * d * kv + q * d + 3 * d * config["intermediate_size"]
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def attention_flops(config: dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context``
    keys, summed over layers: 2 multiply-adds per head dimension each."""
    return (config["num_hidden_layers"] * 4 * config["num_attention_heads"]
            * config["head_dim"] * context)


def token_flops(config: dict, context: int) -> int:
    """Model FLOPs of one token that attends over ``context`` positions
    (itself included)."""
    return 2 * matmul_params(config) + attention_flops(config, context)


def prefill_flops(config: dict, length: int) -> int:
    """A prompt of ``length`` tokens, causal: token i attends over i + 1."""
    return (2 * matmul_params(config) * length
            + attention_flops(config, length * (length + 1) // 2))


def paged_attention_work(config: dict, contexts: Iterable[int],
                         kv_bytes: int = 2, q_bytes: int = 2
                         ) -> Tuple[int, int]:
    """(FLOPs, bytes) one decode step's paged attention needs: for every
    layer and every running request with ``n`` live tokens, read its query
    and the K and V of those ``n`` tokens, write its output.  Pages the
    pool holds beyond the live tokens, and windows that hold none, are no
    part of the work."""
    L = config["num_hidden_layers"]
    H = config["num_attention_heads"]
    KVH = config["num_key_value_heads"]
    D = config["head_dim"]
    flops = 0
    nbytes = 0
    for n in contexts:
        flops += attention_flops(config, n)
        nbytes += L * (2 * n * KVH * D * kv_bytes + 2 * H * D * q_bytes)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the longer of compute at peak and traffic at peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
