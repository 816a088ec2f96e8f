"""Attention math: grouped-query attention for train/prefill (chunked over
queries so 32k-sequence prefill never materializes an S x S score matrix)
and single-token decode attention over a ring-buffer KV cache.

Shapes use the convention
  q: (B, Sq, H, hd)    k, v: (B, Sk, Hkv, hd)    H = Hkv * rep (GQA).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.common import softcap


def _grouped_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """(B,Sq,H,hd) x (B,Sk,Hkv,hd) -> (B,Hkv,rep,Sq,Sk) without repeating k."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    return s * scale


def _grouped_out(p: jax.Array, v: jax.Array, out_dtype) -> jax.Array:
    """(B,Hkv,rep,Sq,Sk) x (B,Sk,Hkv,hd) -> (B,Sq,H,hd)."""
    B, Hkv, rep, Sq, _ = p.shape
    hd = v.shape[-1]
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hkv * rep, hd).astype(out_dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              q_pos: jax.Array, k_pos: jax.Array, *,
              causal: bool = True, window: int = 0,
              attn_softcap: float = 0.0, q_chunk: int = 1024,
              scale: float | None = None) -> jax.Array:
    """GQA attention, chunked over queries.

    q_pos: (B, Sq), k_pos: (B, Sk) absolute positions (-1 = invalid slot).
    """
    B, Sq, H, hd = q.shape
    scale = hd ** -0.5 if scale is None else scale

    def chunk_attn(qc: jax.Array, qpc: jax.Array) -> jax.Array:
        s = _grouped_scores(qc, k, scale)                  # (B,g,r,C,Sk)
        if attn_softcap > 0.0:
            s = softcap(s, attn_softcap)
        ok = k_pos[:, None, :] >= 0
        if causal:
            ok &= k_pos[:, None, :] <= qpc[:, :, None]
        if window > 0:
            ok &= k_pos[:, None, :] > (qpc[:, :, None] - window)
        bias = jnp.where(ok, 0.0, -1e30)                   # (B,C,Sk)
        s = s + bias[:, None, None, :, :]
        p = jax.nn.softmax(s, axis=-1)
        return _grouped_out(p, v, q.dtype)

    if Sq <= q_chunk:
        return chunk_attn(q, q_pos)

    n_chunks = -(-Sq // q_chunk)
    pad = n_chunks * q_chunk - Sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    qs = qp.reshape(B, n_chunks, q_chunk, H, hd).swapaxes(0, 1)
    ps = pp.reshape(B, n_chunks, q_chunk).swapaxes(0, 1)
    outs = jax.lax.map(lambda args: chunk_attn(*args), (qs, ps))
    out = outs.swapaxes(0, 1).reshape(B, n_chunks * q_chunk, H, v.shape[-1])
    return out[:, :Sq]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_pos: jax.Array, pos: jax.Array, *,
                     window: int = 0, attn_softcap: float = 0.0,
                     scale: float | None = None) -> jax.Array:
    """Single-token attention over a ring-buffer KV cache.

    q: (B, H, hd); k_cache/v_cache: (B, W, Hkv, hd);
    cache_pos: (B, W) absolute position stored in each slot (-1 = empty);
    pos: (B,) current absolute position of the query token.
    Returns (B, H, hd).
    """
    B, H, hd = q.shape
    scale = hd ** -0.5 if scale is None else scale
    s = _grouped_scores(q[:, None], k_cache, scale)        # (B,g,r,1,W)
    if attn_softcap > 0.0:
        s = softcap(s, attn_softcap)
    ok = (cache_pos >= 0) & (cache_pos <= pos[:, None])
    if window > 0:
        ok &= cache_pos > (pos[:, None] - window)
    bias = jnp.where(ok, 0.0, -1e30)                       # (B,W)
    s = s + bias[:, None, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_out(p, v_cache, q.dtype)[:, 0]


def latent_decode_attention(q: jax.Array, latent: jax.Array,
                            cache_pos: jax.Array, pos: jax.Array, *,
                            v_dim: int, scale: float) -> jax.Array:
    """Absorbed multi-head latent attention for one query token a row
    (``models.mla``): every head attends over one shared latent cache.

    q: (B, H, D) — the latent query and the rotary query side by side;
    latent: (B, W, D) — the cached latent and rotary key; the values are
    the latent's first ``v_dim`` columns.  Returns (B, H, v_dim).
    """
    s = jnp.einsum("bhd,bwd->bhw", q.astype(jnp.float32),
                   latent.astype(jnp.float32)) * scale
    ok = (cache_pos >= 0) & (cache_pos <= pos[:, None])
    s = jnp.where(ok[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhw,bwd->bhd", p,
                     latent[..., :v_dim].astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pos_pages: jax.Array,
                           block_table: jax.Array, pos: jax.Array, *,
                           window: int = 0, attn_softcap: float = 0.0,
                           scale: float | None = None) -> jax.Array:
    """Single-token attention reading KV through a block table.

    The paged KV layout stores pages of ``ps`` slots in a shared pool;
    each request's logical ring buffer is the concatenation of the
    pages its block table names.  This gathers those pages into the
    dense (B, W) view and runs ``decode_attention`` — bit-identical to
    the contiguous path because the gather is a pure copy (unmapped
    logical pages read with ``cache_pos = -1``, i.e. masked exactly
    like unwritten slots).

    q: (B, H, hd); k_pages/v_pages: (P, ps, Hkv, hd);
    pos_pages: (P, ps) absolute position per pool slot (-1 = empty);
    block_table: (B, n_logical) physical page per logical page
    (-1 = unmapped); pos: (B,) query positions.  Returns (B, H, hd).
    """
    B, n_logical = block_table.shape
    ps = k_pages.shape[1]
    W = n_logical * ps
    bt = jnp.maximum(block_table, 0)
    k_cache = k_pages[bt].reshape(B, W, *k_pages.shape[2:])
    v_cache = v_pages[bt].reshape(B, W, *v_pages.shape[2:])
    mapped = (block_table >= 0)[:, :, None]
    cache_pos = jnp.where(mapped, pos_pages[bt], -1).reshape(B, W)
    return decode_attention(q, k_cache, v_cache, cache_pos, pos,
                            window=window, attn_softcap=attn_softcap,
                            scale=scale)


def cross_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float | None = None) -> jax.Array:
    """Full (non-causal, unmasked) attention to static source embeddings.

    q: (B, Sq, H, hd); k, v: (B, Ssrc, Hkv, hd).
    """
    hd = q.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
    s = _grouped_scores(q, k, scale)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_out(p, v, q.dtype)
