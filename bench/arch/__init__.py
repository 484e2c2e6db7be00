"""Architecture modules, one per model family, and the loader of the
modules a configuration file names.

A serve configuration file names two modules by their paths from the
checkout's root: ``"arch"``, a module of this package, and ``"reference"``,
its plain reference under ``bench/reference/``.  The benchmark loads each
by that path, once, so a configuration of a new family is new files, never
an edit to the driver, the weights or the counts.

An architecture module gives, from the configuration's own keys:

* ``program_config(config)``: the program's ``ModelConfig``;
* ``leaves(config)``: every parameter leaf of the program's tree, by its
  dotted path, as ``(unpadded shape, init)``; ``init`` is ``"ones"`` or
  ``("normal", fan_in)``, a normal with standard deviation
  1/sqrt(fan_in) (``bench/weights.py``);
* ``matmul_params(config)``: the weights one token multiplies;
* ``attention_reads(config, n)``: for a token at context ``n`` (itself
  included), one ``(keys, heads, kv_heads, head_dim)`` per attention
  layer: the keys that layer reads and its head counts
  (``bench/counts.py``).

A reference module gives ``logits(weights, config, tokens, positions,
quantize)`` (``bench/drivers/serve.py``).
"""
from __future__ import annotations

import os

from bench.run import ROOT, load_module

_LOADED: dict = {}


def named(config: dict, key: str):
    """The module at the path ``config[key]``, loaded once a process."""
    path = os.path.join(ROOT, config[key])
    if path not in _LOADED:
        _LOADED[path] = load_module(path, f"bench_{key}_{len(_LOADED)}")
    return _LOADED[path]
