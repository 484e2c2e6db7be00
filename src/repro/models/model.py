"""Model assembly: config → (specs, init, train/prefill/decode functions).

Layers are grouped into *superblocks* of ``period`` layers (the lcm of the
architecture's interleave periods: jamba = 8, xlstm = 6, homogeneous = 1) and
scanned with per-superblock ``jax.checkpoint`` — HLO stays O(period) and the
backward stores one activation per superblock boundary.

Decode threads per-layer state (KV caches / SSM states / xLSTM states)
through the same superblock scan as stacked pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import layers as L
from . import mamba as M
from . import moe as MoE
from . import xlstm as X
from .common import Spec, init_params, logical_tree, spec_shapes
from .config import ModelConfig, RunConfig
from ..distributed.sharding import with_logical_constraint

PyTree = Any


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def padded_vocab(cfg: ModelConfig) -> int:
    return _round_up(cfg.vocab, 256)


def block_period(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return int(np.lcm(cfg.attn_every, cfg.moe_every))
    if cfg.family == "xlstm" and cfg.slstm_every:
        return cfg.slstm_every
    return 1


def n_superblocks(cfg: ModelConfig) -> int:
    per = block_period(cfg)
    assert cfg.n_layers % per == 0, (cfg.n_layers, per)
    return cfg.n_layers // per


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def _mixer_kind(cfg: ModelConfig, j: int) -> str:
    if cfg.family == "xlstm":
        return "slstm" if cfg.is_slstm_layer(j) else "mlstm"
    if cfg.family == "hybrid" and not cfg.is_attn_layer(j):
        return "mamba"
    return "attn"


def _ffn_kind(cfg: ModelConfig, j: int) -> str:
    if cfg.d_ff <= 0:
        return "none"
    return "moe" if cfg.is_moe_layer(j) else "mlp"


def _position_specs(cfg: ModelConfig, rc: RunConfig, j: int) -> dict:
    d = cfg.d_model
    b: dict = {"ln1": Spec((d,), (None,), init="ones")}
    mk = _mixer_kind(cfg, j)
    if mk == "attn":
        b["attn"] = L.attention_specs(cfg)
    elif mk == "mamba":
        b["mamba"] = M.mamba_specs(cfg)
    elif mk == "mlstm":
        b["mlstm"] = X.mlstm_specs(cfg)
    elif mk == "slstm":
        b["slstm"] = X.slstm_specs(cfg)
    fk = _ffn_kind(cfg, j)
    if fk != "none":
        b["ln2"] = Spec((d,), (None,), init="ones")
        b["moe" if fk == "moe" else "mlp"] = (
            MoE.moe_specs(cfg, rc) if fk == "moe" else L.mlp_specs(cfg))
    return b


def _stack(tree: PyTree, n: int) -> PyTree:
    def f(s: Spec) -> Spec:
        return Spec((n,) + s.shape, ("layers",) + s.logical, init=s.init,
                    scale=s.scale, dtype=s.dtype)
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, Spec))


def model_specs(cfg: ModelConfig, rc: RunConfig) -> dict:
    d, V = cfg.d_model, padded_vocab(cfg)
    per = block_period(cfg)
    nsb = n_superblocks(cfg)
    blocks = {f"pos{j}": _position_specs(cfg, rc, j) for j in range(per)}
    s: dict = {
        "embed": Spec((V, d), ("vocab", "embed"), init="embed", scale=0.02),
        "blocks": _stack(blocks, nsb),
        "final_norm": Spec((d,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = Spec((d, V), ("embed", "vocab"))
    return s


# ---------------------------------------------------------------------------
# decode-state (cache) structure
# ---------------------------------------------------------------------------

def decode_state_shapes(cfg: ModelConfig, rc: RunConfig, batch: int,
                        max_seq: int, dtype=jnp.bfloat16) -> dict:
    """Abstract decode state per superblock position, stacked over nsb."""
    per = block_period(cfg)
    nsb = n_superblocks(cfg)
    out: dict = {}
    for j in range(per):
        mk = _mixer_kind(cfg, j)
        if mk == "attn":
            kv = jax.ShapeDtypeStruct(
                (nsb, batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype)
            out[f"pos{j}"] = {"k": kv, "v": kv}
        elif mk == "mamba":
            mc = cfg.mamba
            d_in = mc.expand * cfg.d_model
            out[f"pos{j}"] = {
                "conv": jax.ShapeDtypeStruct((nsb, batch, mc.d_conv - 1, d_in),
                                             dtype),
                "ssm": jax.ShapeDtypeStruct((nsb, batch, d_in, mc.d_state),
                                            jnp.float32),
            }
        elif mk == "mlstm":
            d_in = 2 * cfg.d_model
            H = cfg.n_heads
            dh = d_in // H
            out[f"pos{j}"] = {
                "conv": jax.ShapeDtypeStruct((nsb, batch, 3, d_in), dtype),
                "C": jax.ShapeDtypeStruct((nsb, batch, H, dh, dh), jnp.float32),
                "n": jax.ShapeDtypeStruct((nsb, batch, H, dh), jnp.float32),
                "m": jax.ShapeDtypeStruct((nsb, batch, H), jnp.float32),
            }
        elif mk == "slstm":
            d = cfg.d_model
            out[f"pos{j}"] = {
                "c": jax.ShapeDtypeStruct((nsb, batch, d), jnp.float32),
                "n": jax.ShapeDtypeStruct((nsb, batch, d), jnp.float32),
                "m": jax.ShapeDtypeStruct((nsb, batch, d), jnp.float32),
                "h": jax.ShapeDtypeStruct((nsb, batch, d), jnp.float32),
            }
    return out


def decode_state_logical(cfg: ModelConfig) -> dict:
    """Logical axes for the decode state (for dry-run shardings)."""
    per = block_period(cfg)
    out: dict = {}
    for j in range(per):
        mk = _mixer_kind(cfg, j)
        if mk == "attn":
            kv = ("layers", "batch", "kv_seq", "kv_heads", "kv_head_dim")
            out[f"pos{j}"] = {"k": kv, "v": kv}
        elif mk == "mamba":
            out[f"pos{j}"] = {
                "conv": ("layers", "batch", None, "kv_head_dim"),
                "ssm": ("layers", "batch", "kv_head_dim", None)}
        elif mk == "mlstm":
            out[f"pos{j}"] = {
                "conv": ("layers", "batch", None, None),
                "C": ("layers", "batch", None, None, None),
                "n": ("layers", "batch", None, None),
                "m": ("layers", "batch", None)}
        elif mk == "slstm":
            out[f"pos{j}"] = {k: ("layers", "batch", None)
                              for k in ("c", "n", "m", "h")}
    return out


def init_decode_state(cfg: ModelConfig, rc: RunConfig, batch: int,
                      max_seq: int, dtype=jnp.bfloat16) -> dict:
    shapes = decode_state_shapes(cfg, rc, batch, max_seq, dtype)
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    # exponential-gating stabilizer states start at -inf, not 0 (xLSTM)
    for pos, st in state.items():
        if "m" in st and "C" in st or ("m" in st and "h" in st):
            st["m"] = jnp.full_like(st["m"], -1e30)
    return state


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    rc: RunConfig
    mesh: Optional[Mesh] = None
    act_rules: str = "default"

    # ---- params ----
    def specs(self) -> dict:
        return model_specs(self.cfg, self.rc)

    def init(self, seed: int = 0) -> PyTree:
        return init_params(self.specs(), seed=seed, dtype=self.rc.param_dtype)

    def logical(self) -> PyTree:
        return logical_tree(self.specs())

    def abstract_params(self) -> PyTree:
        return spec_shapes(self.specs(), dtype=self.rc.param_dtype)

    # ---- helpers ----
    def _constrain(self, x, logical):
        return with_logical_constraint(x, logical, self.mesh, self.act_rules)

    def _embed(self, params, tokens, patch_embeds=None):
        cdt = jnp.dtype(self.rc.compute_dtype)
        x = params["embed"].astype(cdt)[tokens]
        if self.cfg.n_patches and patch_embeds is not None:
            np_ = min(self.cfg.n_patches, x.shape[1])
            x = jax.lax.dynamic_update_slice(
                x, patch_embeds[:, :np_].astype(cdt), (0, 0, 0))
        return self._constrain(x, ("batch", "seq", "embed"))

    def _logits(self, params, x):
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        V = padded_vocab(self.cfg)
        if V != self.cfg.vocab:  # mask padding classes
            pad_mask = jnp.arange(V) >= self.cfg.vocab
            logits = jnp.where(pad_mask, -1e9, logits.astype(jnp.float32))
        return logits

    def _mixer(self, j, p, x, positions, *, state=None, return_state=False):
        cfg, rc = self.cfg, self.rc
        mk = _mixer_kind(cfg, j)
        if mk == "attn":
            if state is None:
                out = L.attention_layer(cfg, p["attn"], x, positions,
                                        q_chunk=rc.attn_q_chunk,
                                        kv_chunk=rc.attn_kv_chunk)
                return (out, None) if return_state else out
            return self._attn_decode(p["attn"], x, positions, state,
                                     return_state)
        if mk == "mamba":
            st = (state["conv"], state["ssm"]) if state is not None else None
            r = M.mamba_layer(cfg, p["mamba"], x, scan_chunk=rc.scan_chunk,
                              state=st, return_state=return_state)
            if return_state:
                out, (cs, ss) = r
                return out, {"conv": cs, "ssm": ss}
            return r
        if mk == "mlstm":
            st = X.MLSTMState(state["conv"], state["C"], state["n"],
                              state["m"]) if state is not None else None
            r = X.mlstm_layer(cfg, p["mlstm"], x, scan_chunk=rc.scan_chunk,
                              state=st, return_state=return_state)
            if return_state:
                out, s = r
                return out, {"conv": s.conv, "C": s.C, "n": s.n, "m": s.m}
            return r
        if mk == "slstm":
            st = X.SLSTMState(state["c"], state["n"], state["m"],
                              state["h"]) if state is not None else None
            r = X.slstm_layer(cfg, p["slstm"], x, scan_chunk=rc.scan_chunk,
                              state=st, return_state=return_state)
            if return_state:
                out, s = r
                return out, {"c": s.c, "n": s.n, "m": s.m, "h": s.h}
            return r
        raise ValueError(mk)

    def _attn_decode(self, p, x, positions, state, return_state):
        """Single-token attention against the dense KV cache."""
        cfg = self.cfg
        q, k, v = L.attention_qkv(cfg, p, x, positions)
        kv_len = positions[:, 0]                     # [B]
        B = x.shape[0]
        bidx = jnp.arange(B)
        k_cache = state["k"]
        v_cache = state["v"]
        k_cache = k_cache.at[bidx, kv_len].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, kv_len].set(v[:, 0].astype(v_cache.dtype))
        # NOTE: no sharding constraint here — the cache inherits the input
        # sharding through the aliased scan carry; a mid-scan constraint made
        # the SPMD partitioner insert per-layer "involuntary full remat"
        # copies of the whole cache slice (§Perf iteration 2).
        o = L.decode_attention(q, k_cache.astype(q.dtype),
                               v_cache.astype(q.dtype), kv_len + 1)
        out = jnp.einsum("bse,ed->bsd", o.reshape(B, 1, cfg.q_dim), p["wo"])
        if return_state:
            return out, {"k": k_cache, "v": v_cache}
        return out

    def _ffn(self, j, p, x):
        fk = _ffn_kind(self.cfg, j)
        if fk == "none":
            return x * 0.0, jnp.float32(0.0)
        if fk == "moe":
            return MoE.moe_ffn(self.cfg, self.rc, p["moe"], x,
                               mesh=self.mesh, act_rules=self.act_rules)
        return L.mlp(p["mlp"], x), jnp.float32(0.0)

    def _superblock(self, p_sb, x, positions, *, states=None,
                    return_states=False):
        cfg = self.cfg
        per = block_period(cfg)
        aux = jnp.float32(0.0)
        new_states: Dict[str, Any] = {}
        for j in range(per):
            p = p_sb[f"pos{j}"]
            st = states.get(f"pos{j}") if states is not None else None
            h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
            r = self._mixer(j, p, h, positions, state=st,
                            return_state=return_states or st is not None)
            if isinstance(r, tuple):
                mix_out, new_st = r
                if return_states:
                    new_states[f"pos{j}"] = new_st
            else:
                mix_out = r
            x = x + mix_out
            if _ffn_kind(cfg, j) != "none":
                h = L.rms_norm(x, p["ln2"], cfg.rms_eps)
                f, a = self._ffn(j, p, h)
                x = x + f
                aux = aux + a
            x = self._constrain(x, ("batch", "seq", "embed"))
        return x, aux, new_states

    # ---- public passes ----
    def backbone(self, params, tokens, *, patch_embeds=None,
                 input_embeds=None, positions=None):
        """Full-sequence forward → (final hidden [B, S, d], moe aux loss)."""
        cfg, rc = self.cfg, self.rc
        cdt = jnp.dtype(rc.compute_dtype)
        if input_embeds is not None:
            x = input_embeds.astype(cdt)
            B, S = x.shape[:2]
        else:
            B, S = tokens.shape
            x = self._embed(params, tokens, patch_embeds)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))

        def body(x, p_sb):
            x, aux, _ = self._superblock(
                jax.tree.map(lambda a: a.astype(cdt) if a.dtype in
                             (jnp.float32, jnp.bfloat16) else a, p_sb),
                x, positions)
            return x, aux

        body_fn = jax.checkpoint(body) if rc.remat == "full" else body
        x, auxs = jax.lax.scan(body_fn, x, params["blocks"])
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
        return x, jnp.sum(auxs)

    def forward(self, params, tokens, *, patch_embeds=None,
                input_embeds=None, positions=None):
        """Full-sequence forward → logits [B, S, V]. (inference / tests)"""
        x, aux = self.backbone(params, tokens, patch_embeds=patch_embeds,
                               input_embeds=input_embeds, positions=positions)
        return self._logits(params, x), aux

    def loss(self, params, tokens, labels, *, mask=None, patch_embeds=None,
             input_embeds=None, xent_chunk: int = 512):
        """Training loss with seq-chunked lm-head + cross-entropy: never
        materializes [B, S, V] (vocab 152k x seq 4k in fp32 would be ~5GB per
        device otherwise).  Returns (loss, moe_aux)."""
        cfg = self.cfg
        x, aux = self.backbone(params, tokens, patch_embeds=patch_embeds,
                               input_embeds=input_embeds)
        B, S, d = x.shape
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        head = head.astype(x.dtype)
        V = padded_vocab(cfg)
        pad_bias = jnp.where(jnp.arange(V) >= cfg.vocab, -1e9, 0.0
                             ).astype(jnp.float32)
        C = min(xent_chunk, S)
        padS = (-S) % C
        xs = jnp.pad(x, ((0, 0), (0, padS), (0, 0))).reshape(B, -1, C, d)
        ls = jnp.pad(labels, ((0, 0), (0, padS))).reshape(B, -1, C)
        if mask is None:
            mask = jnp.ones((B, S), jnp.float32)
        ms = jnp.pad(mask, ((0, 0), (0, padS))).reshape(B, -1, C)
        nc = xs.shape[1]

        def chunk(carry, idx):
            xc = xs[:, idx]
            lc = ls[:, idx]
            mc = ms[:, idx]
            logits = (jnp.einsum("bcd,dv->bcv", xc, head)
                      .astype(jnp.float32) + pad_bias)
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            nll = ((lse - ll) * mc).sum()
            return carry + nll, None

        total, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.float32(0.0),
                                jnp.arange(nc))
        denom = jnp.maximum(mask.sum(), 1.0)
        return total / denom, aux

    def prefill(self, params, tokens, *, patch_embeds=None,
                input_embeds=None, max_seq: Optional[int] = None):
        """Forward that also returns the decode state filled to S tokens."""
        cfg, rc = self.cfg, self.rc
        cdt = jnp.dtype(rc.compute_dtype)
        if input_embeds is not None:
            x = input_embeds.astype(cdt)
            B, S = x.shape[:2]
        else:
            B, S = tokens.shape
            x = self._embed(params, tokens, patch_embeds)
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        max_seq = max_seq or S

        per = block_period(cfg)

        def body(x, p_sb):
            p_sb = jax.tree.map(lambda a: a.astype(cdt) if a.dtype in
                                (jnp.float32, jnp.bfloat16) else a, p_sb)
            states: Dict[str, Any] = {}
            aux = jnp.float32(0.0)
            for j in range(per):
                p = p_sb[f"pos{j}"]
                h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
                mk = _mixer_kind(cfg, j)
                if mk == "attn":
                    q, k, v = L.attention_qkv(cfg, p["attn"], h, positions)
                    o = L.chunked_attention(q, k, v, causal=cfg.causal,
                                            q_chunk=rc.attn_q_chunk,
                                            kv_chunk=rc.attn_kv_chunk)
                    mix = jnp.einsum("bse,ed->bsd",
                                     o.reshape(B, S, cfg.q_dim),
                                     p["attn"]["wo"])
                    pad = max_seq - S
                    kc = jnp.pad(k.astype(cdt), ((0, 0), (0, pad), (0, 0), (0, 0)))
                    vc = jnp.pad(v.astype(cdt), ((0, 0), (0, pad), (0, 0), (0, 0)))
                    states[f"pos{j}"] = {"k": kc, "v": vc}
                else:
                    mix, st = self._mixer(j, p, h, positions,
                                          return_state=True)
                    states[f"pos{j}"] = st
                x = x + mix
                if _ffn_kind(cfg, j) != "none":
                    hh = L.rms_norm(x, p["ln2"], cfg.rms_eps)
                    f, a = self._ffn(j, p, hh)
                    x = x + f
                    aux = aux + a
                x = self._constrain(x, ("batch", "seq", "embed"))
            return x, (aux, states)

        x, (auxs, states) = jax.lax.scan(body, x, params["blocks"])
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
        return self._logits(params, x), states

    def prefill_chunked(self, params, tokens, *, n_chunks: int,
                        patch_embeds=None, input_embeds=None,
                        max_seq: Optional[int] = None):
        """Sarathi-style chunked prefill: process the sequence in ``n_chunks``
        passes, each attending to the KV cache filled so far.

        Peak activation transients shrink ~n_chunks× (per-chunk MoE dispatch
        buffers, attention workspaces); compute is unchanged because each
        chunk attends only to the statically-sliced cache prefix.
        """
        cfg, rc = self.cfg, self.rc
        cdt = jnp.dtype(rc.compute_dtype)
        if input_embeds is not None:
            x_full = input_embeds.astype(cdt)
            B, S = x_full.shape[:2]
        else:
            B, S = tokens.shape
            x_full = self._embed(params, tokens, patch_embeds)
        assert S % n_chunks == 0, (S, n_chunks)
        Sc = S // n_chunks
        max_seq = max_seq or S
        state = init_decode_state(cfg, rc, B, max_seq, cdt)
        per = block_period(cfg)

        def attn_chunk(p, h, st, ci):
            off = ci * Sc
            positions = jnp.broadcast_to(off + jnp.arange(Sc), (B, Sc))
            q, k, v = L.attention_qkv(cfg, p, h, positions)
            kc = jax.lax.dynamic_update_slice(
                st["k"], k.astype(st["k"].dtype), (0, off, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                st["v"], v.astype(st["v"].dtype), (0, off, 0, 0))
            # static prefix slice: no wasted compute on unfilled cache
            o = L.chunked_attention(
                q, kc[:, :off + Sc].astype(q.dtype),
                vc[:, :off + Sc].astype(q.dtype),
                causal=cfg.causal, q_offset=off,
                q_chunk=rc.attn_q_chunk, kv_chunk=rc.attn_kv_chunk)
            out = jnp.einsum("bse,ed->bsd", o.reshape(B, Sc, cfg.q_dim),
                             p["wo"])
            return out, {"k": kc, "v": vc}

        hidden_chunks = []
        for ci in range(n_chunks):
            xc = jax.lax.dynamic_slice_in_dim(x_full, ci * Sc, Sc, axis=1)
            positions = jnp.broadcast_to(ci * Sc + jnp.arange(Sc), (B, Sc))

            def body(x, xs, _ci=ci):
                p_sb, st_sb = xs
                p_sb = jax.tree.map(lambda a: a.astype(cdt) if a.dtype in
                                    (jnp.float32, jnp.bfloat16) else a, p_sb)
                new_states: Dict[str, Any] = {}
                for j in range(per):
                    p = p_sb[f"pos{j}"]
                    st = st_sb[f"pos{j}"]
                    h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
                    if _mixer_kind(cfg, j) == "attn":
                        mix, new_st = attn_chunk(p["attn"], h, st, _ci)
                    else:
                        mix, new_st = self._mixer(j, p, h, positions,
                                                  state=st, return_state=True)
                    new_states[f"pos{j}"] = new_st
                    x = x + mix
                    if _ffn_kind(cfg, j) != "none":
                        hh = L.rms_norm(x, p["ln2"], cfg.rms_eps)
                        f, _ = self._ffn(j, p, hh)
                        x = x + f
                    x = self._constrain(x, ("batch", "seq", "embed"))
                return x, new_states

            xc, state = jax.lax.scan(body, xc, (params["blocks"], state))
            hidden_chunks.append(xc)

        h = jnp.concatenate(hidden_chunks, axis=1)
        h = L.rms_norm(h, params["final_norm"], cfg.rms_eps)
        return self._logits(params, h), state

    def decode_step_paged(self, params, state, tokens, kv_len, block_tables,
                          descriptors, *, page_size: int,
                          K_classes: Tuple[int, ...],
                          interpret: Optional[bool] = None):
        """One decode step against the PAGED KV cache (the paper's path).

        ``state``: as ``decode_step`` but attention positions hold
        {"pool_k","pool_v"} of shape [nsb, *kv_pool_shape(n_pages, T, KVH,
        D)] (``repro.kernels.paged_attention.paged_attention``'s layout);
        ``block_tables``: [B, max_pages] int32 (shared by all layers);
        ``descriptors``: per-class (win_idx, covered) arrays from
        ``repro.kernels.paged_attention.ops.build_descriptors``;
        ``interpret`` defaults to :func:`repro.kernels.default_interpret`.
        """
        from ..kernels import default_interpret
        from ..kernels.paged_attention.ops import _paged_attention_jit
        from ..kernels.paged_attention.paged_attention import to_pool_layout
        if interpret is None:
            interpret = default_interpret()
        cfg, rc = self.cfg, self.rc
        cdt = jnp.dtype(rc.compute_dtype)
        B = tokens.shape[0]
        x = self._embed(params, tokens)
        positions = kv_len[:, None]
        classes = tuple(sorted(set(list(K_classes) + [0]), reverse=True))
        desc_flat = []
        for k in classes:
            wi, cov = descriptors[k]
            desc_flat += [jnp.asarray(wi), jnp.asarray(cov)]
        bt = jnp.asarray(block_tables)
        bidx = jnp.arange(B)
        page_of = bt[bidx, kv_len // page_size]
        off_of = kv_len % page_size
        # inactive batch slots carry all -1 block tables; a raw scatter at
        # page -1 would wrap to the LAST pool page and corrupt whichever
        # live sequence owns it.  Route them to an out-of-range page and
        # drop: a clamped index would collide with an active lane writing
        # the same cell, and duplicate-index scatter order is unspecified.
        lane_ok = page_of >= 0

        def paged_attn(p, h, st):
            q, k, v = L.attention_qkv(cfg, p, h, positions)
            n_pool = st["pool_k"].shape[0]
            drop_page = jnp.where(lane_ok, page_of, n_pool)
            pk = st["pool_k"].at[drop_page, off_of].set(
                to_pool_layout(k[:, 0]).astype(st["pool_k"].dtype),
                mode="drop")
            pv = st["pool_v"].at[drop_page, off_of].set(
                to_pool_layout(v[:, 0]).astype(st["pool_v"].dtype),
                mode="drop")
            o = _paged_attention_jit(
                q[:, 0], pk, pv, kv_len + 1, tuple(desc_flat),
                page_size=page_size, classes=classes, interpret=interpret)
            out = jnp.einsum("bse,ed->bsd",
                             o[:, None].astype(h.dtype).reshape(B, 1, cfg.q_dim),
                             p["wo"])
            return out, {"pool_k": pk, "pool_v": pv}

        per = block_period(cfg)

        def body(x, xs):
            p_sb, st_sb = xs
            p_sb = jax.tree.map(lambda a: a.astype(cdt) if a.dtype in
                                (jnp.float32, jnp.bfloat16) else a, p_sb)
            new_states: Dict[str, Any] = {}
            for j in range(per):
                p = p_sb[f"pos{j}"]
                st = st_sb[f"pos{j}"]
                h = L.rms_norm(x, p["ln1"], cfg.rms_eps)
                if _mixer_kind(cfg, j) == "attn":
                    mix, new_st = paged_attn(p["attn"], h, st)
                else:
                    mix, new_st = self._mixer(j, p, h, positions, state=st,
                                              return_state=True)
                new_states[f"pos{j}"] = new_st
                x = x + mix
                if _ffn_kind(cfg, j) != "none":
                    hh = L.rms_norm(x, p["ln2"], cfg.rms_eps)
                    f, _ = self._ffn(j, p, hh)
                    x = x + f
            return x, new_states

        x, new_states = jax.lax.scan(body, x, (params["blocks"], state))
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
        return self._logits(params, x), new_states

    def decode_step(self, params, state, tokens, kv_len):
        """One decode step: tokens [B, 1], kv_len [B] → (logits, new state)."""
        cfg, rc = self.cfg, self.rc
        cdt = jnp.dtype(rc.compute_dtype)
        x = self._embed(params, tokens)
        positions = kv_len[:, None]

        def body(x, xs):
            p_sb, st_sb = xs
            p_sb = jax.tree.map(lambda a: a.astype(cdt) if a.dtype in
                                (jnp.float32, jnp.bfloat16) else a, p_sb)
            x, _, new_st = self._superblock(p_sb, x, positions,
                                            states=st_sb, return_states=True)
            return x, new_st

        x, new_states = jax.lax.scan(body, x, (params["blocks"], state))
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
        return self._logits(params, x), new_states


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is not None:
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return -ll.mean()
