"""Seeded random weights for a dense decoder, made on the device.

One jitted call makes every leaf from the seed, in the dtype it is served
in.  The tree is the layout the serving program takes (``embed``,
``blocks.pos0.{ln1, ln2, attn.{wq, wk, wv, wo}, mlp.{w_gate, w_up,
w_down}}``, ``final_norm``, ``lm_head``; layers stacked on the leading
axis); :func:`make` checks it leaf by leaf against the program's abstract
parameters.  Rows of the vocabulary past ``vocab_size`` (the program pads
it) are zero.  Matrices are normal with standard deviation
1/sqrt(fan-in); the embedding is normal with standard deviation 1; the
norms' gains are 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def logical_shapes(config: dict) -> dict:
    """Every leaf's unpadded shape, keyed by its path."""
    d = config["hidden_size"]
    L = config["num_hidden_layers"]
    D = config["head_dim"]
    q = config["num_attention_heads"] * D
    kv = config["num_key_value_heads"] * D
    ff = config["intermediate_size"]
    V = config["vocab_size"]
    return {
        "embed": (V, d), "lm_head": (d, V), "final_norm": (d,),
        "blocks.pos0.ln1": (L, d), "blocks.pos0.ln2": (L, d),
        "blocks.pos0.attn.wq": (L, d, q), "blocks.pos0.attn.wk": (L, d, kv),
        "blocks.pos0.attn.wv": (L, d, kv), "blocks.pos0.attn.wo": (L, q, d),
        "blocks.pos0.mlp.w_gate": (L, d, ff), "blocks.pos0.mlp.w_up":
            (L, d, ff), "blocks.pos0.mlp.w_down": (L, ff, d)}


def _flatten(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, path + "."))
        else:
            out[path] = v
    return out


def _unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, *, shapes, dtype):
    flat = {}
    for i, (path, logical, padded) in enumerate(shapes):
        if path.endswith(("ln1", "ln2", "final_norm")):
            leaf = jnp.ones(logical, jnp.float32)
        else:
            fan_in = 1 if path == "embed" else logical[-2]
            leaf = jax.random.normal(jax.random.fold_in(key, i), logical,
                                     jnp.float32) / np.sqrt(fan_in)
        pad = [(0, p - n) for n, p in zip(logical, padded)]
        flat[path] = jnp.pad(leaf, pad).astype(dtype)
    return _unflatten(flat)


def make(config: dict, seed: int, abstract_params: dict, dtype) -> dict:
    """The weights for ``seed`` in the program's layout; raises if that
    layout holds a leaf this file does not know or shapes that do not
    pad the published ones."""
    abstract = _flatten(abstract_params)
    logical = logical_shapes(config)
    if set(abstract) != set(logical):
        raise ValueError(f"the program's parameters {sorted(abstract)} are "
                         f"not the leaves {sorted(logical)}")
    shapes = []
    for path in sorted(logical):
        padded = tuple(abstract[path].shape)
        if len(padded) != len(logical[path]) or any(
                p < n for p, n in zip(padded, logical[path])):
            raise ValueError(f"{path}: program shape {padded} does not hold "
                             f"{logical[path]}")
        shapes.append((path, logical[path], padded))
    key = jax.random.key(np.random.default_rng(seed).integers(2**31))
    return _make(key, shapes=tuple(shapes), dtype=jnp.dtype(dtype))
