"""Seeded random weights for a served model, made on the device.

One jitted call makes every leaf from the seed, in the dtype it is served
in.  The configuration's architecture module (``bench/arch/``) lists the
leaves of the tree the serving program takes, each with its unpadded shape
and its init; :func:`make` checks that list leaf by leaf against the
program's abstract parameters.  Leaf ``i`` of the sorted paths draws from
the seed's key folded with ``i``.  Rows of the vocabulary past
``vocab_size`` (the program pads it) are zero.  An init is ``"ones"`` or
``("normal", fan_in)``: normal with standard deviation 1/sqrt(fan_in).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch import named


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
    for i, (path, logical, padded, init) in enumerate(shapes):
        if init == "ones":
            leaf = jnp.ones(logical, jnp.float32)
        else:
            _, fan_in = init
            leaf = jax.random.normal(jax.random.fold_in(key, i), logical,
                                     jnp.float32) / np.sqrt(fan_in)
        pad = [(0, p - n) for n, p in zip(logical, padded)]
        flat[path] = jnp.pad(leaf, pad).astype(dtype)
    return _unflatten(flat)


def make(config: dict, seed: int, abstract_params: dict, dtype) -> dict:
    """The weights for ``seed`` in the program's layout; raises if that
    layout holds a leaf the architecture module does not list or shapes
    that do not pad the published ones."""
    abstract = _flatten(abstract_params)
    leaves = named(config, "arch").leaves(config)
    if set(abstract) != set(leaves):
        raise ValueError(f"the program's parameters {sorted(abstract)} are "
                         f"not the leaves {sorted(leaves)}")
    shapes = []
    for path in sorted(leaves):
        logical, init = leaves[path]
        padded = tuple(abstract[path].shape)
        if len(padded) != len(logical) or any(
                p < n for p, n in zip(padded, logical)):
            raise ValueError(f"{path}: program shape {padded} does not hold "
                             f"{logical}")
        shapes.append((path, tuple(logical), padded, init))
    key = jax.random.key(np.random.default_rng(seed).integers(2**31))
    return _make(key, shapes=tuple(shapes), dtype=jnp.dtype(dtype))
