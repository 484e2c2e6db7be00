"""Dense decoder: in every layer grouped-query attention over every
earlier token and a SwiGLU feed-forward; an untied output head.

The configuration file's keys give the sizes: ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``vocab_size``, ``rope_theta``,
``rms_norm_eps``, ``tie_word_embeddings``.  The interface is in
``bench/arch/__init__.py``.
"""
from __future__ import annotations


def program_config(config: dict):
    """The program's configuration object."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab=config["vocab_size"], rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"])


def leaves(config: dict) -> dict:
    """Every leaf's unpadded shape and init, keyed by its path; layers
    are stacked on the leading axis.  Norm gains are ones, the embedding
    a normal of fan-in 1, every other matrix a normal over its fan-in."""
    d = config["hidden_size"]
    L = config["num_hidden_layers"]
    D = config["head_dim"]
    q = config["num_attention_heads"] * D
    kv = config["num_key_value_heads"] * D
    ff = config["intermediate_size"]
    V = config["vocab_size"]
    shapes = {
        "embed": (V, d), "lm_head": (d, V), "final_norm": (d,),
        "blocks.pos0.ln1": (L, d), "blocks.pos0.ln2": (L, d),
        "blocks.pos0.attn.wq": (L, d, q), "blocks.pos0.attn.wk": (L, d, kv),
        "blocks.pos0.attn.wv": (L, d, kv), "blocks.pos0.attn.wo": (L, q, d),
        "blocks.pos0.mlp.w_gate": (L, d, ff), "blocks.pos0.mlp.w_up":
            (L, d, ff), "blocks.pos0.mlp.w_down": (L, ff, d)}
    norms = ("blocks.pos0.ln1", "blocks.pos0.ln2", "final_norm")
    return {path: (shape, "ones" if path in norms else
                   ("normal", 1 if path == "embed" else shape[-2]))
            for path, shape in shapes.items()}


def matmul_params(config: dict) -> int:
    """Weights that every token multiplies: each layer's projections and
    feed-forward, and the output head (the embedding is a lookup)."""
    d = config["hidden_size"]
    D = config["head_dim"]
    q = config["num_attention_heads"] * D
    kv = config["num_key_value_heads"] * D
    layer = d * q + 2 * d * kv + q * d + 3 * d * config["intermediate_size"]
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def attention_reads(config: dict, n: int) -> list:
    """Every layer attends over all ``n`` tokens of the context."""
    return [(n, config["num_attention_heads"],
             config["num_key_value_heads"], config["head_dim"])
            ] * config["num_hidden_layers"]
