"""Plain reference of a dense GQA decoder (InternLM2, arXiv:2403.17297).

The forward pass as the published description gives it, in float32 at the
highest matmul precision, one layer at a time: RMSNorm, rotary embeddings
(the rotate-half form over the head's two halves, base ``rope_theta``),
grouped-query causal attention, a SwiGLU feed-forward, a final RMSNorm and
an untied output head.  It imports nothing of the program under test and
reads only the weights the benchmark made from the seed.

``quantize="fp8"`` is the control: every matmul's inputs are rounded to
float8 (e4m3) with a scale per row of the activations and per output
column of the weights, the nearest precision below the configuration's
bfloat16, and the rest is as above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8 e4m3 value
Q_CHUNK = 1024           # query rows per attention block


def _round_fp8(x, axis):
    """x rounded to float8 e4m3, scaled so each slice along ``axis``
    spans the format's range."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, b, quantize):
    """a [.., n] @ b [n, m] in float32."""
    if quantize == "fp8":
        a = _round_fp8(a, axis=-1)
        b = _round_fp8(b, axis=0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g


def _rope(x, theta):
    """x [S, heads, D] at positions 0..S-1, rotate-half form."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta", "quantize"))
def _layer(x, p, *, heads, kv_heads, eps, theta, quantize):
    S, d = x.shape
    D = p["wq"].shape[1] // heads
    p = jax.tree.map(lambda a: a.astype(F32), p)
    h = _rms(x, p["ln1"], eps)
    q = _rope(_mm(h, p["wq"], quantize).reshape(S, heads, D), theta)
    k = _rope(_mm(h, p["wk"], quantize).reshape(S, kv_heads, D), theta)
    v = _mm(h, p["wv"], quantize).reshape(S, kv_heads, D)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for lo in range(0, S, Q_CHUNK):
        qc = q[lo: lo + Q_CHUNK]
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HIGHEST) / np.sqrt(D)
        qpos = lo + jnp.arange(qc.shape[0])
        causal = qpos[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST))
    o = jnp.concatenate(outs, axis=0).reshape(S, heads * D)
    x = x + _mm(o, p["wo"], quantize)
    h = _rms(x, p["ln2"], eps)
    f = jax.nn.silu(_mm(h, p["w_gate"], quantize)) * _mm(h, p["w_up"],
                                                           quantize)
    return x + _mm(f, p["w_down"], quantize)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "quantize"))
def _head(x, norm, head, *, vocab, eps, quantize):
    x = _rms(x, norm.astype(F32), eps)
    return _mm(x, head[:, :vocab].astype(F32), quantize)


def logits(weights: dict, config: dict, tokens, positions,
           quantize: str = "none"):
    """Logits [len(positions), vocab] that the model assigns after each
    of ``positions`` of the token sequence ``tokens``."""
    blocks = weights["blocks"]["pos0"]
    kw = dict(heads=config["num_attention_heads"],
              kv_heads=config["num_key_value_heads"],
              eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
              quantize=quantize)
    x = weights["embed"][jnp.asarray(tokens)].astype(F32)
    for i in range(config["num_hidden_layers"]):
        p = {"ln1": blocks["ln1"][i], "ln2": blocks["ln2"][i],
             **{n: blocks["attn"][n][i] for n in ("wq", "wk", "wv", "wo")},
             **{n: blocks["mlp"][n][i]
                for n in ("w_gate", "w_up", "w_down")}}
        x = _layer(x, p, **kw)
    return _head(x[jnp.asarray(positions)], weights["final_norm"],
                 weights["lm_head"], vocab=config["vocab_size"],
                 eps=config["rms_norm_eps"], quantize=quantize)
