"""Composable transformer assembly.

Builds every assigned architecture from the layer kinds in
``repro.config.LAYER_KINDS``.  The repeated ``block_pattern`` is executed
with ``jax.lax.scan`` over stacked parameters so HLO size and compile time
are O(pattern length), not O(n_layers) — essential for 100-layer configs
lowered on a 512-device mesh.

Public entry points:
  init_params(cfg, key, dtype)
  forward_train(params, cfg, tokens, ...)        -> (logits, aux_loss)
  init_cache(cfg, batch, max_seq, dtype)         -> cache pytree
  prefill(params, cfg, tokens, max_seq, ...)     -> (last_logits, cache)
  decode_step(params, cfg, tokens, cache, pos, ...) -> (logits, new_cache)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import MLA_KINDS, ModelConfig
from repro.models import attention as attn_lib
from repro.models.common import (activation, apply_rope, dense_init, rms_norm,
                                 softcap, split_keys)
from repro.models.ffn import gated_ffn
from repro.models.mla import init_mla, mla_decode_sublayer, mla_sublayer
from repro.models.moe import moe_ffn
from repro.models.rglru import rglru_block, rglru_block_step
from repro.models.ssd import ssd_block, ssd_block_step

# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def _init_ffn(key, cfg: ModelConfig, dtype, dense: bool = False) -> dict:
    """A layer's FFN: the MoE where the model has one (the routed experts
    this layer holds), else — or with ``dense`` — a gated FFN of
    ``d_ff``."""
    d = cfg.d_model
    if cfg.moe is not None and not dense:
        m = cfg.moe
        ks = split_keys(key, 12)
        p = {
            "router": dense_init(ks[0], (d, m.n_experts), jnp.float32),
            "we1": dense_init(ks[1], (m.held, d, m.d_ff_expert), dtype),
            "we3": dense_init(ks[2], (m.held, d, m.d_ff_expert), dtype),
            "we2": dense_init(ks[3], (m.held, m.d_ff_expert, d), dtype),
        }
        if m.n_shared_experts:
            ff_s = m.d_ff_shared
            p.update({
                "ws1": dense_init(ks[4], (d, ff_s), dtype),
                "ws3": dense_init(ks[5], (d, ff_s), dtype),
                "ws2": dense_init(ks[6], (ff_s, d), dtype),
            })
            if m.shared_gated:
                p["shared_gate"] = dense_init(ks[7], (d,), jnp.float32,
                                              scale=0.02)
        if m.d_ff_dense_residual:
            ff_d = m.d_ff_dense_residual
            p.update({
                "wd1": dense_init(ks[8], (d, ff_d), dtype),
                "wd3": dense_init(ks[9], (d, ff_d), dtype),
                "wd2": dense_init(ks[10], (ff_d, d), dtype),
            })
        if m.scoring == "sigmoid":  # the correction bias, N(0, 0.01^2)
            p["score_bias"] = 0.01 * jax.random.normal(
                ks[11], (m.n_experts,), jnp.float32)
        return p
    return {
        "w1": dense_init(jax.random.fold_in(key, 1), (d, cfg.d_ff), dtype),
        "w3": dense_init(jax.random.fold_in(key, 2), (d, cfg.d_ff), dtype),
        "w2": dense_init(jax.random.fold_in(key, 3), (cfg.d_ff, d), dtype),
    }


def _init_attn_proj(key, cfg: ModelConfig, dtype, prefix="") -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = split_keys(key, 4)
    return {
        prefix + "wq": dense_init(ks[0], (d, cfg.n_heads * hd), dtype),
        prefix + "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd), dtype),
        prefix + "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd), dtype),
        prefix + "wo": dense_init(ks[3], (cfg.n_heads * hd, d), dtype),
    }


def init_layer_params(key, kind: str, cfg: ModelConfig, dtype) -> dict:
    d = cfg.d_model
    ks = split_keys(key, 6)
    zeros = lambda *s: jnp.zeros(s, dtype)
    p = {"ln1": zeros(d), "ln2": zeros(d)}
    if cfg.use_post_norm:
        p["ln1_post"] = zeros(d)
        p["ln2_post"] = zeros(d)

    if kind in ("attn", "local"):
        p.update(_init_attn_proj(ks[0], cfg, dtype))
        p.update(_init_ffn(ks[1], cfg, dtype))
    elif kind in MLA_KINDS:
        p.update(init_mla(ks[0], cfg, dtype))
        p.update(_init_ffn(ks[1], cfg, dtype, dense=kind == "mla_dense"))
    elif kind == "cross":  # llama-3.2-vision gated cross-attention layer
        p.update(_init_attn_proj(ks[0], cfg, dtype))
        p.update(_init_ffn(ks[1], cfg, dtype))
        p["ln_kv"] = zeros(d)
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_ffn"] = jnp.zeros((), jnp.float32)
    elif kind == "selfcross":  # whisper decoder layer
        p.update(_init_attn_proj(ks[0], cfg, dtype))
        p.update(_init_attn_proj(ks[1], cfg, dtype, prefix="c_"))
        p.update(_init_ffn(ks[2], cfg, dtype))
        p["ln_cross"] = zeros(d)
    elif kind == "rglru":
        r = cfg.rglru
        w = r.lru_width
        p.update({
            "w_in_x": dense_init(ks[0], (d, w), dtype),
            "w_in_gate": dense_init(ks[1], (d, w), dtype),
            "conv_w": dense_init(ks[2], (r.conv_width, w), dtype, scale=0.5),
            "w_a": dense_init(ks[3], (w, w), jnp.float32),
            "b_a": jnp.zeros((w,), jnp.float32),
            "w_x": dense_init(ks[4], (w, w), jnp.float32),
            "b_x": jnp.zeros((w,), jnp.float32),
            "lam": jnp.full((w,), 0.5, jnp.float32),
            "w_out": dense_init(ks[5], (w, d), dtype),
        })
        p.update(_init_ffn(jax.random.fold_in(key, 99), cfg, dtype))
    elif kind == "ssd":
        s = cfg.ssm
        di, h, n = s.d_inner(d), s.n_heads(d), s.d_state
        p = {"ln1": zeros(d)}
        p.update({
            "in_proj": dense_init(ks[0], (d, 2 * di + 2 * n + h), dtype),
            "conv_w": dense_init(ks[1], (s.conv_width, di + 2 * n), dtype, scale=0.5),
            "dt_bias": jnp.log(jnp.expm1(
                jnp.linspace(1e-3, 0.1, h, dtype=jnp.float32))),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
            "D": jnp.ones((h,), jnp.float32),
            "norm": zeros(di),
            "out_proj": dense_init(ks[2], (di, d), dtype),
        })
    else:
        raise ValueError(kind)
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    keys = split_keys(key, 6)
    d = cfg.d_model
    params = {
        "embed": dense_init(keys[0], (cfg.vocab, d), dtype, scale=0.02),
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], (d, cfg.vocab), dtype)

    def stacked(kind, key):
        ks = jnp.stack(split_keys(key, cfg.n_blocks))
        return jax.vmap(lambda k: init_layer_params(k, kind, cfg, dtype))(ks)

    params["blocks"] = tuple(
        stacked(kind, jax.random.fold_in(keys[2], i))
        for i, kind in enumerate(cfg.block_pattern))
    params["remainder"] = tuple(
        init_layer_params(jax.random.fold_in(keys[3], i), kind, cfg, dtype)
        for i, kind in enumerate(cfg.remainder_pattern))
    if cfg.lead_pattern:
        params["lead"] = tuple(
            init_layer_params(jax.random.fold_in(keys[5], i), kind, cfg,
                              dtype)
            for i, kind in enumerate(cfg.lead_pattern))

    if cfg.encoder is not None:
        enc_keys = split_keys(keys[4], cfg.encoder.n_layers + 2)
        enc_blocks = jax.vmap(
            lambda k: init_layer_params(k, "attn", cfg, dtype)
        )(jnp.stack(enc_keys[:cfg.encoder.n_layers]))
        params["encoder"] = {
            "blocks": enc_blocks,
            "pos_embed": dense_init(enc_keys[-1],
                                    (cfg.encoder.source_len, d), dtype, scale=0.02),
            "final_norm": jnp.zeros((d,), jnp.float32).astype(dtype),
        }
    return params


# ---------------------------------------------------------------------------
# layer application — sequence mode (train / prefill)
# ---------------------------------------------------------------------------


def _maybe_post(p, name, y, cfg):
    if cfg.use_post_norm:
        return rms_norm(y, p[name])
    return y


def _ffn_sublayer(p, x2d_shape_x, cfg: ModelConfig, capacity_mode: str):
    """x: (B, T, d) -> (delta, aux)."""
    x = x2d_shape_x
    B, T, d = x.shape
    h = rms_norm(x, p["ln2"])
    if "router" in p:
        y, aux = moe_ffn(p, h.reshape(B * T, d), cfg.moe, cfg.act, capacity_mode)
        y = y.reshape(B, T, d)
    else:
        y = gated_ffn(h, p["w1"], p["w3"], p["w2"], cfg.act)
        aux = jnp.zeros((), jnp.float32)
    return _maybe_post(p, "ln2_post", y, cfg), aux


@jax.named_scope("attention")
def _self_attn_sublayer(p, x, cfg: ModelConfig, positions, *, causal=True,
                        window=0, build_cache=False, cache_len=0, prefix=""):
    """Returns (delta, cache_entry_or_None)."""
    B, T, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    h = rms_norm(x, p["ln1"])
    q = (h @ p[prefix + "wq"]).reshape(B, T, H, hd)
    k = (h @ p[prefix + "wk"]).reshape(B, T, Hkv, hd)
    v = (h @ p[prefix + "wv"]).reshape(B, T, Hkv, hd)
    if causal:  # decoder-style layers use RoPE; whisper encoder uses learned pos
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = attn_lib.attention(q, k, v, positions, positions, causal=causal,
                             window=window, attn_softcap=cfg.attn_softcap)
    delta = out.reshape(B, T, H * hd) @ p[prefix + "wo"]
    delta = _maybe_post(p, "ln1_post", delta, cfg)
    cache = None
    if build_cache:
        W = cache_len
        n_keep = min(T, W)
        slots = positions[0, T - n_keep:] % W
        k_c = jnp.zeros((B, W, Hkv, hd), k.dtype).at[:, slots].set(k[:, T - n_keep:])
        v_c = jnp.zeros((B, W, Hkv, hd), v.dtype).at[:, slots].set(v[:, T - n_keep:])
        pos_c = jnp.full((B, W), -1, jnp.int32).at[:, slots].set(
            positions[:, T - n_keep:].astype(jnp.int32))
        cache = {"k": k_c, "v": v_c, "pos": pos_c}
    return delta, cache


def _cross_kv(p, cfg, source, prefix=""):
    B, S, _ = source.shape
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    src = rms_norm(source, p["ln_kv"]) if "ln_kv" in p else source
    k = (src @ p[prefix + "wk"]).reshape(B, S, Hkv, hd)
    v = (src @ p[prefix + "wv"]).reshape(B, S, Hkv, hd)
    return k, v


def apply_layer_seq(kind: str, p: dict, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array, *, source: Optional[jax.Array],
                    capacity_mode: str, build_cache: bool, max_seq: int,
                    causal: bool = True):
    """One layer over a full sequence.  Returns (x, cache_entry, aux)."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else 0
        W = min(cfg.window, max_seq) if kind == "local" else max_seq
        delta, cache = _self_attn_sublayer(
            p, x, cfg, positions, causal=causal, window=window,
            build_cache=build_cache, cache_len=W)
        x = x + delta
        dff, aux = _ffn_sublayer(p, x, cfg, capacity_mode)
        x = x + dff
    elif kind in MLA_KINDS:
        delta, cache = mla_sublayer(p, cfg, x, positions,
                                    build_cache=build_cache,
                                    cache_len=max_seq)
        x = x + delta
        dff, aux = _ffn_sublayer(p, x, cfg, capacity_mode)
        x = x + dff
    elif kind == "cross":
        B, T, d = x.shape
        H, hd = cfg.n_heads, cfg.resolved_head_dim
        h = rms_norm(x, p["ln1"])
        q = (h @ p["wq"]).reshape(B, T, H, hd)
        k, v = _cross_kv(p, cfg, source)
        out = attn_lib.cross_attention(q, k, v).reshape(B, T, H * hd)
        x = x + (jnp.tanh(p["gate_attn"]) * (out @ p["wo"])).astype(x.dtype)
        dff, aux = _ffn_sublayer(p, x, cfg, capacity_mode)
        x = x + (jnp.tanh(p["gate_ffn"]) * dff).astype(x.dtype)
        if build_cache:
            cache = {"k_src": k, "v_src": v}
    elif kind == "selfcross":
        delta, cache_self = _self_attn_sublayer(
            p, x, cfg, positions, causal=True, window=0,
            build_cache=build_cache, cache_len=max_seq)
        x = x + delta
        B, T, d = x.shape
        H, hd = cfg.n_heads, cfg.resolved_head_dim
        h = rms_norm(x, p["ln_cross"])
        q = (h @ p["c_wq"]).reshape(B, T, H, hd)
        k, v = _cross_kv(p, cfg, source, prefix="c_")
        out = attn_lib.cross_attention(q, k, v).reshape(B, T, H * hd)
        x = x + out @ p["c_wo"]
        dff, aux = _ffn_sublayer(p, x, cfg, capacity_mode)
        x = x + dff
        if build_cache:
            cache = dict(cache_self, k_src=k, v_src=v)
    elif kind == "rglru":
        h = rms_norm(x, p["ln1"])
        gelu = lambda t: activation(t, "gelu")
        y, state = rglru_block(p, h, cfg.rglru, gelu, None)
        x = x + y
        dff, aux = _ffn_sublayer(p, x, cfg, capacity_mode)
        x = x + dff
        cache = state if build_cache else None
    elif kind == "ssd":
        h = rms_norm(x, p["ln1"])
        y, state = ssd_block(p, h, cfg.ssm, cfg.d_model, None)
        x = x + y
        cache = state if build_cache else None
    else:
        raise ValueError(kind)
    return x, cache, aux


# ---------------------------------------------------------------------------
# layer application — decode mode (single token)
# ---------------------------------------------------------------------------


@jax.named_scope("attention")
def self_attn_decode_sublayer(p: dict, cfg: ModelConfig, x: jax.Array,
                              pos: jax.Array, cache: dict, window: int,
                              prefix: str = "", ln: str = "ln1",
                              use_kernels: bool = False):
    """Decode-mode self-attention sublayer (shared with the disaggregated
    runtime).  x: (B, d).  Returns (delta, new_kv_cache).

    ``use_kernels`` routes the attention read through the Pallas
    flash-decode kernel (``kernels.decode_attention``) instead of the
    jnp path; the jnp function stays the oracle, so the flag must be
    threaded explicitly rather than swapped inside ``models.attention``.
    """
    B, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    h = rms_norm(x, p[ln])
    q = (h @ p[prefix + "wq"]).reshape(B, H, hd)
    k = (h @ p[prefix + "wk"]).reshape(B, Hkv, hd)
    v = (h @ p[prefix + "wv"]).reshape(B, Hkv, hd)
    q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    W = cache["k"].shape[1]
    b_idx = jnp.arange(B)
    slot = pos % W
    k_c = cache["k"].at[b_idx, slot].set(k.astype(cache["k"].dtype))
    v_c = cache["v"].at[b_idx, slot].set(v.astype(cache["v"].dtype))
    pos_c = cache["pos"].at[b_idx, slot].set(pos.astype(jnp.int32))
    if use_kernels:
        from repro.kernels import ops as kops  # lazy: no module cycle
        out = kops.decode_attention(q, k_c, v_c, pos_c, pos, window=window,
                                    attn_softcap=cfg.attn_softcap)
    else:
        out = attn_lib.decode_attention(q, k_c, v_c, pos_c, pos,
                                        window=window,
                                        attn_softcap=cfg.attn_softcap)
    delta = out.reshape(B, H * hd) @ p[prefix + "wo"]
    return _maybe_post(p, "ln1_post", delta, cfg), {"k": k_c, "v": v_c,
                                                    "pos": pos_c}


def ffn_decode_sublayer(p: dict, cfg: ModelConfig, x: jax.Array,
                        capacity_mode: str):
    """Decode-mode FFN sublayer.  Returns (delta, aux)."""
    h = rms_norm(x, p["ln2"])
    if "router" in p:
        y, aux = moe_ffn(p, h, cfg.moe, cfg.act, capacity_mode)
    else:
        y = gated_ffn(h, p["w1"], p["w3"], p["w2"], cfg.act)
        aux = jnp.zeros((), jnp.float32)
    return _maybe_post(p, "ln2_post", y, cfg), aux


def apply_layer_decode(kind: str, p: dict, cfg: ModelConfig, x: jax.Array,
                       pos: jax.Array, cache: dict, capacity_mode: str,
                       use_kernels: bool = False):
    """One layer for one token.  x: (B, d), pos: (B,) int32.

    Returns (x, new_cache_entry, aux)."""
    B, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    aux = jnp.zeros((), jnp.float32)

    def self_attn_decode(p, x, cache, window, prefix="", ln="ln1"):
        return self_attn_decode_sublayer(p, cfg, x, pos, cache, window,
                                         prefix=prefix, ln=ln,
                                         use_kernels=use_kernels)

    def ffn_decode(p, x):
        return ffn_decode_sublayer(p, cfg, x, capacity_mode)

    if kind in ("attn", "local"):
        window = cfg.window if kind == "local" else 0
        delta, cache = self_attn_decode(p, x, cache, window)
        x = x + delta
        dff, aux = ffn_decode(p, x)
        x = x + dff
    elif kind in MLA_KINDS:
        delta, cache = mla_decode_sublayer(p, cfg, x, pos, cache,
                                           use_kernels=use_kernels)
        x = x + delta
        dff, aux = ffn_decode(p, x)
        x = x + dff
    elif kind == "cross":
        h = rms_norm(x, p["ln1"])
        q = (h @ p["wq"]).reshape(B, 1, H, hd)
        out = attn_lib.cross_attention(q, cache["k_src"], cache["v_src"])
        x = x + (jnp.tanh(p["gate_attn"])
                 * (out.reshape(B, H * hd) @ p["wo"])).astype(x.dtype)
        dff, aux = ffn_decode(p, x)
        x = x + (jnp.tanh(p["gate_ffn"]) * dff).astype(x.dtype)
    elif kind == "selfcross":
        delta, new_self = self_attn_decode(
            p, x, {k: cache[k] for k in ("k", "v", "pos")}, 0)
        x = x + delta
        h = rms_norm(x, p["ln_cross"])
        q = (h @ p["c_wq"]).reshape(B, 1, H, hd)
        out = attn_lib.cross_attention(q, cache["k_src"], cache["v_src"])
        x = x + out.reshape(B, H * hd) @ p["c_wo"]
        dff, aux = ffn_decode(p, x)
        x = x + dff
        cache = dict(new_self, k_src=cache["k_src"], v_src=cache["v_src"])
    elif kind == "rglru":
        h = rms_norm(x, p["ln1"])
        gelu = lambda t: activation(t, "gelu")
        y, cache = rglru_block_step(p, h, cfg.rglru, gelu, cache)
        x = x + y
        dff, aux = ffn_decode(p, x)
        x = x + dff
    elif kind == "ssd":
        h = rms_norm(x, p["ln1"])
        y, cache = ssd_block_step(p, h, cfg.ssm, cfg.d_model, cache)
        x = x + y
    else:
        raise ValueError(kind)
    return x, cache, aux


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------


def init_cache_entry(kind: str, cfg: ModelConfig, batch: int, max_seq: int,
                     dtype) -> dict:
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    def kv(W):
        return {"k": jnp.zeros((batch, W, Hkv, hd), dtype),
                "v": jnp.zeros((batch, W, Hkv, hd), dtype),
                "pos": jnp.full((batch, W), -1, jnp.int32)}

    if kind == "attn":
        return kv(max_seq)
    if kind in MLA_KINDS:
        return {"latent": jnp.zeros((batch, max_seq, cfg.mla.latent_dim),
                                    dtype),
                "pos": jnp.full((batch, max_seq), -1, jnp.int32)}
    if kind == "local":
        return kv(min(cfg.window, max_seq))
    if kind == "cross":
        S = cfg.cross_source_len or (cfg.encoder.source_len if cfg.encoder else 0)
        return {"k_src": jnp.zeros((batch, S, Hkv, hd), dtype),
                "v_src": jnp.zeros((batch, S, Hkv, hd), dtype)}
    if kind == "selfcross":
        S = cfg.encoder.source_len if cfg.encoder else cfg.cross_source_len
        return dict(kv(max_seq),
                    k_src=jnp.zeros((batch, S, Hkv, hd), dtype),
                    v_src=jnp.zeros((batch, S, Hkv, hd), dtype))
    if kind == "rglru":
        r = cfg.rglru
        return {"h": jnp.zeros((batch, r.lru_width), jnp.float32),
                "conv": jnp.zeros((batch, r.conv_width - 1, r.lru_width), dtype)}
    if kind == "ssd":
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        return {"ssm": jnp.zeros((batch, s.n_heads(cfg.d_model), s.head_dim,
                                  s.d_state), jnp.float32),
                "conv": jnp.zeros((batch, s.conv_width - 1, di + 2 * s.d_state),
                                  dtype)}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> dict:
    def stack(entry):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_blocks,) + a.shape).copy(), entry)

    cache = {
        "blocks": tuple(
            stack(init_cache_entry(kind, cfg, batch, max_seq, dtype))
            for kind in cfg.block_pattern),
        "remainder": tuple(
            init_cache_entry(kind, cfg, batch, max_seq, dtype)
            for kind in cfg.remainder_pattern),
    }
    if cfg.lead_pattern:
        cache["lead"] = tuple(
            init_cache_entry(kind, cfg, batch, max_seq, dtype)
            for kind in cfg.lead_pattern)
    return cache


# ---------------------------------------------------------------------------
# full model passes
# ---------------------------------------------------------------------------


def _encode(params: dict, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Whisper-style encoder over stubbed frame embeddings (B, S, d)."""
    enc = params["encoder"]
    x = frames + enc["pos_embed"][None]
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(x, lp):
        x, _, _ = apply_layer_seq("attn", lp, cfg, x, positions, source=None,
                                  capacity_mode="full", build_cache=False,
                                  max_seq=S, causal=False)
        return x, None

    x, _ = _scan_blocks(body, x, enc["blocks"], cfg.encoder.n_layers)
    return rms_norm(x, enc["final_norm"])


@jax.named_scope("embed")
def _embed_tokens(params, cfg, tokens):
    x = params["embed"][tokens]
    if cfg.tie_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


@jax.named_scope("lm_head")
def _lm_head(params, cfg, x):
    h = rms_norm(x, params["final_norm"])
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ w.astype(h.dtype)
    return softcap(logits, cfg.logit_softcap) if cfg.logit_softcap else logits


# When True, lax.scan over blocks is fully unrolled.  Compile time grows
# O(n_layers), but XLA's cost_analysis then counts every layer (it counts a
# while-loop body exactly once) — the dry-run sets this for exact rooflines.
UNROLL_BLOCKS = False

# Optional PartitionSpec constraint applied to activations at layer
# boundaries (Megatron-style sequence parallelism when set to
# P(data, "model", None)): XLA then lowers the TP all-reduce pairs into
# reduce-scatter + all-gather, halving per-layer collective bytes.
ACT_SPEC = None


def _constrain_acts(x):
    if ACT_SPEC is not None and x.ndim == len(ACT_SPEC):
        return jax.lax.with_sharding_constraint(x, ACT_SPEC)
    return x


def _scan_blocks(body, init, xs, n: int):
    return jax.lax.scan(body, init, xs, unroll=n if UNROLL_BLOCKS else 1)


def _seq_pass(params, cfg: ModelConfig, x, positions, source, capacity_mode,
              build_cache, max_seq, remat: str):
    pattern = cfg.block_pattern
    aux0 = jnp.zeros((), jnp.float32)

    lead_caches = []
    for i, kind in enumerate(cfg.lead_pattern):
        x, c, a = apply_layer_seq(kind, params["lead"][i], cfg, x, positions,
                                  source=source, capacity_mode=capacity_mode,
                                  build_cache=build_cache, max_seq=max_seq)
        aux0 = aux0 + a
        lead_caches.append(c)

    def body(carry, bp):
        x, aux = carry
        caches = []
        for i, kind in enumerate(pattern):
            x, c, a = apply_layer_seq(kind, bp[i], cfg, x, positions,
                                      source=source, capacity_mode=capacity_mode,
                                      build_cache=build_cache, max_seq=max_seq)
            x = _constrain_acts(x)
            aux = aux + a
            caches.append(c)
        return (x, aux), tuple(caches) if build_cache else None

    if remat == "full":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    (x, aux), block_caches = _scan_blocks(body, (x, aux0), params["blocks"],
                                          cfg.n_blocks)

    rem_caches = []
    for i, kind in enumerate(cfg.remainder_pattern):
        x, c, a = apply_layer_seq(kind, params["remainder"][i], cfg, x,
                                  positions, source=source,
                                  capacity_mode=capacity_mode,
                                  build_cache=build_cache, max_seq=max_seq)
        aux = aux + a
        rem_caches.append(c)
    cache = ({"blocks": block_caches, "remainder": tuple(rem_caches)}
             if build_cache else None)
    if build_cache and cfg.lead_pattern:
        cache["lead"] = tuple(lead_caches)
    return x, aux, cache


def forward_hidden(params: dict, cfg: ModelConfig, tokens: jax.Array,
                   cross_embeds: Optional[jax.Array] = None,
                   frames: Optional[jax.Array] = None,
                   remat: str = "full", capacity_mode: str = "train"):
    """Full-sequence forward up to (but excluding) the LM head.

    Returns (hidden (B,T,d), aux_loss scalar).  Used by the training loop's
    chunked cross-entropy so (B,T,V) logits are never fully materialized."""
    B, T = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    source = cross_embeds
    if cfg.encoder is not None:
        assert frames is not None, f"{cfg.name} needs encoder frames"
        source = _encode(params, cfg, frames)
    x, aux, _ = _seq_pass(params, cfg, x, positions, source, capacity_mode,
                          build_cache=False, max_seq=T, remat=remat)
    return x, aux


def forward_train(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  cross_embeds: Optional[jax.Array] = None,
                  frames: Optional[jax.Array] = None,
                  remat: str = "full", capacity_mode: str = "train"):
    """Full-sequence forward.  tokens: (B, T) int32.

    Returns (logits (B,T,V), aux_loss scalar)."""
    x, aux = forward_hidden(params, cfg, tokens, cross_embeds, frames,
                            remat, capacity_mode)
    return _lm_head(params, cfg, x), aux


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array, max_seq: int,
            cross_embeds: Optional[jax.Array] = None,
            frames: Optional[jax.Array] = None,
            capacity_mode: str = "auto"):
    """Prefill pass building the decode cache.

    capacity_mode "auto": drop-free ("full") for small batches where
    exactness is cheap; bounded "eval" capacity (2.5x fair share) at scale
    — a 1M-token prefill with C=T would spend ExT expert slots on K*T
    routed tokens.  Returns (last-token logits (B, V), cache)."""
    B, T = tokens.shape
    if capacity_mode == "auto":
        capacity_mode = "full" if B * T <= 2048 else "eval"
    x = _embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    source = cross_embeds
    if cfg.encoder is not None:
        assert frames is not None
        source = _encode(params, cfg, frames)
    x, _, cache = _seq_pass(params, cfg, x, positions, source, capacity_mode,
                            build_cache=True, max_seq=max_seq, remat="none")
    return _lm_head(params, cfg, x[:, -1]), cache


def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                cache: dict, pos: jax.Array, capacity_mode: str = "full",
                use_kernels: bool = False):
    """One decode step.  tokens: (B,) int32, pos: (B,) int32.

    Returns (logits (B, V), new_cache)."""
    x = _embed_tokens(params, cfg, tokens)
    pattern = cfg.block_pattern

    new_lead = []
    for i, kind in enumerate(cfg.lead_pattern):
        x, c, _ = apply_layer_decode(kind, params["lead"][i], cfg, x, pos,
                                     cache["lead"][i], capacity_mode,
                                     use_kernels=use_kernels)
        new_lead.append(c)

    def body(x, xs):
        bp, bc = xs
        new_caches = []
        for i, kind in enumerate(pattern):
            x, c, _ = apply_layer_decode(kind, bp[i], cfg, x, pos, bc[i],
                                         capacity_mode,
                                         use_kernels=use_kernels)
            new_caches.append(c)
        return x, tuple(new_caches)

    x, new_block_caches = _scan_blocks(body, x,
                                       (params["blocks"], cache["blocks"]),
                                       cfg.n_blocks)

    new_rem = []
    for i, kind in enumerate(cfg.remainder_pattern):
        x, c, _ = apply_layer_decode(kind, params["remainder"][i], cfg, x, pos,
                                     cache["remainder"][i], capacity_mode,
                                     use_kernels=use_kernels)
        new_rem.append(c)
    new_cache = {"blocks": new_block_caches, "remainder": tuple(new_rem)}
    if cfg.lead_pattern:
        new_cache["lead"] = tuple(new_lead)
    return _lm_head(params, cfg, x), new_cache
