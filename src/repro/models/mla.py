"""Multi-head latent attention (MLA, DeepSeek-V2/V3).

Per layer, with ``h`` the normed input:

  c_q = RMSNorm(h W_qa),  q = c_q W_qb            per head: [q_nope | q_pe]
  [c_kv | k_pe] = h W_kva,  c_kv = RMSNorm(c_kv)  k_pe shared by all heads
  [k_nope | v] = c_kv W_kvb                       per head
  q_pe and k_pe are rotated (YaRN frequencies where the config scales
  rope), and q . k runs over [nope | rope] = ``qk_head_dim`` dimensions.

Prefill expands keys and values per head and runs the usual attention.
Decode absorbs W_kvb instead: q_nope goes through W_uk (W_kvb's key
columns) into the latent space, the scores are q_lat . c_kv + q_pe . k_pe
over the cached ``[c_kv | k_pe]``, and the latent output goes through
W_uv (W_kvb's value columns) before W_o.  So the cache holds
``kv_lora_rank + qk_rope_head_dim`` values a position and layer, the
entry ``{"latent": (B, W, kv_lora_rank + rope), "pos": (B, W)}``.

The rotary columns use this repository's convention (the two halves of
``qk_rope_head_dim``), not HF's interleaved pairs: under random weights
that is a fixed permutation of W_qb's and W_kva's rotary columns.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn_lib
from repro.models.common import (apply_rope, dense_init, rms_norm,
                                 split_keys, yarn_frequencies,
                                 yarn_rope_scale, yarn_softmax_scale)


def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    a, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    ks = split_keys(key, 5)
    return {
        "wq_a": dense_init(ks[0], (d, a.q_lora_rank), dtype),
        "q_norm": jnp.zeros((a.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], (a.q_lora_rank, H * a.qk_head_dim), dtype),
        "wkv_a": dense_init(ks[2], (d, a.latent_dim), dtype),
        "kv_norm": jnp.zeros((a.kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks[3], (a.kv_lora_rank, H * (
            a.qk_nope_head_dim + a.v_head_dim)), dtype),
        "wo": dense_init(ks[4], (H * a.v_head_dim, d), dtype),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    return yarn_softmax_scale(cfg.mla.qk_head_dim, cfg.rope_scaling)


def _rope(x, positions, cfg: ModelConfig):
    """Rotate x (..., T, heads, rope) at ``positions`` (..., T)."""
    y = cfg.rope_scaling
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta)
    freqs = jnp.asarray(yarn_frequencies(x.shape[-1], cfg.rope_theta, y))
    out = apply_rope(x, positions, cfg.rope_theta, freqs)
    scale = yarn_rope_scale(y)
    return out if scale == 1.0 else (out * scale).astype(out.dtype)


def _project(p, cfg: ModelConfig, h, positions):
    """h: (B, T, d).  Returns q_nope (B,T,H,nope), rotated q_pe
    (B,T,H,rope), normed c_kv (B,T,L) and rotated k_pe (B,T,rope)."""
    a, H = cfg.mla, cfg.n_heads
    B, T, _ = h.shape
    c_q = rms_norm(h @ p["wq_a"], p["q_norm"])
    q = (c_q @ p["wq_b"]).reshape(B, T, H, a.qk_head_dim)
    q_nope, q_pe = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
    kv = h @ p["wkv_a"]
    c_kv = rms_norm(kv[..., :a.kv_lora_rank], p["kv_norm"])
    k_pe = _rope(kv[..., None, a.kv_lora_rank:], positions, cfg)[..., 0, :]
    return q_nope, _rope(q_pe, positions, cfg), c_kv, k_pe


def _kv_b(p, cfg: ModelConfig):
    """W_kvb as (L, H, nope + v): per head, W_uk then W_uv."""
    a = cfg.mla
    return p["wkv_b"].reshape(a.kv_lora_rank, cfg.n_heads,
                              a.qk_nope_head_dim + a.v_head_dim)


@jax.named_scope("attention")
def mla_sublayer(p, cfg: ModelConfig, x, positions, *, build_cache=False,
                 cache_len=0):
    """Expanded MLA over a sequence.  x: (B, T, d).  Returns (delta,
    cache entry or None)."""
    a, H = cfg.mla, cfg.n_heads
    B, T, _ = x.shape
    q_nope, q_pe, c_kv, k_pe = _project(p, cfg, rms_norm(x, p["ln1"]),
                                        positions)
    kv = jnp.einsum("btl,lhe->bthe", c_kv, _kv_b(p, cfg))
    k_nope, v = kv[..., :a.qk_nope_head_dim], kv[..., a.qk_nope_head_dim:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None], (B, T, H, a.qk_rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    out = attn_lib.attention(q, k, v, positions, positions,
                             scale=softmax_scale(cfg))
    delta = out.reshape(B, T, H * a.v_head_dim) @ p["wo"]
    cache = None
    if build_cache:
        W = cache_len
        n_keep = min(T, W)
        slots = positions[0, T - n_keep:] % W
        latent = jnp.concatenate([c_kv, k_pe], axis=-1)[:, T - n_keep:]
        cache = {
            "latent": jnp.zeros((B, W, a.latent_dim), latent.dtype)
            .at[:, slots].set(latent),
            "pos": jnp.full((B, W), -1, jnp.int32).at[:, slots].set(
                positions[:, T - n_keep:].astype(jnp.int32))}
    return delta, cache


@jax.named_scope("attention")
def mla_decode_sublayer(p, cfg: ModelConfig, x, pos, cache, *,
                        use_kernels: bool = False):
    """Absorbed MLA for one token a row.  x: (B, d), pos: (B,).  Returns
    (delta, new cache entry)."""
    a, H = cfg.mla, cfg.n_heads
    B, _ = x.shape
    q_nope, q_pe, c_kv, k_pe = _project(p, cfg, rms_norm(x, p["ln1"])[:, None],
                                        pos[:, None])
    w = _kv_b(p, cfg)
    q_lat = jnp.einsum("bhn,lhn->bhl", q_nope[:, 0],
                       w[..., :a.qk_nope_head_dim])
    q = jnp.concatenate([q_lat, q_pe[:, 0].astype(q_lat.dtype)], axis=-1)
    W = cache["latent"].shape[1]
    b_idx = jnp.arange(B)
    slot = pos % W
    latent = cache["latent"].at[b_idx, slot].set(
        jnp.concatenate([c_kv[:, 0], k_pe[:, 0]], axis=-1)
        .astype(cache["latent"].dtype))
    pos_c = cache["pos"].at[b_idx, slot].set(pos.astype(jnp.int32))
    if use_kernels:
        from repro.kernels import ops as kops  # lazy: no module cycle
        o_lat = kops.mla_decode_attention(q, latent, pos_c, pos,
                                          v_dim=a.kv_lora_rank,
                                          scale=softmax_scale(cfg))
    else:
        o_lat = attn_lib.latent_decode_attention(
            q, latent, pos_c, pos, v_dim=a.kv_lora_rank,
            scale=softmax_scale(cfg))
    o = jnp.einsum("bhl,lhv->bhv", o_lat.astype(w.dtype),
                   w[..., a.qk_nope_head_dim:])
    delta = o.reshape(B, H * a.v_head_dim) @ p["wo"]
    return delta, {"latent": latent, "pos": pos_c}
