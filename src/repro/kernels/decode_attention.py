"""Flash-decode GQA attention Pallas TPU kernel.

The attention-node hot loop during decoding: one query token per request
attends over its (ring-buffer) KV cache.  This is memory-bound — the
kernel's job is to stream the KV cache HBM->VMEM exactly once per step
with an online-softmax accumulator resident in VMEM.

Layout: q (B, Hkv, rep, hd); k/v cache (B, W, Hkv, hd), read in
(Wb, Hkv, hd) blocks that hold every kv-head — the block's last two dims
are the cache's own (Hkv, hd), which Mosaic accepts and which match the
cache's HBM tiling, so no relayout copy of the cache is made.  Grid
(B, W/Wb) with the KV-length dimension innermost; the kernel walks the
kv-heads of a block in a static loop, each with its (rep, hd) f32
accumulator and (rep, 1) running max/denominator kept in scratch across
KV blocks.  Each request's decode position rides in SMEM as a
scalar-prefetch argument.

``mla_decode_attention`` is the same online softmax for multi-head
latent attention: one latent cache row shared by every head, whose
first columns are also the values.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode


def _attend_block(q_ref, k_ref, v_ref, ok, o_ref, acc_ref, m_ref, l_ref, *,
                  w_step, nw: int, attn_softcap: float, scale: float):
    """One (Wb, Hkv, hd) KV block of the online softmax, every kv-head.
    ``ok``: (1, Wb) mask of the slots this query may attend to."""
    @pl.when(w_step == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    okf = ok.astype(jnp.float32)
    for g in range(q_ref.shape[0]):
        q = q_ref[g].astype(jnp.float32)                   # (rep, hd)
        k = k_ref[:, g, :].astype(jnp.float32)             # (Wb, hd)
        v = v_ref[:, g, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if attn_softcap > 0.0:
            s = attn_softcap * jnp.tanh(s / attn_softcap)
        s = jnp.where(ok, s, -1e30)

        m_old = m_ref[g]                                   # (rep, 1)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new) * okf
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[g] = (acc_ref[g] * alpha
                      + jax.lax.dot_general(
                          p, v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
        m_ref[g] = m_new

    @pl.when(w_step == nw - 1)
    def _():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _valid(cpos, pos, window: int):
    ok = (cpos >= 0) & (cpos <= pos)
    if window > 0:
        ok &= cpos > (pos - window)
    return ok


def _kernel(pos_ref, q_ref, k_ref, v_ref, cpos_ref, o_ref,
            acc_ref, m_ref, l_ref, *, nw: int, window: int,
            attn_softcap: float, scale: float):
    ok = _valid(cpos_ref[...], pos_ref[pl.program_id(0)], window)
    _attend_block(q_ref, k_ref, v_ref, ok, o_ref, acc_ref, m_ref, l_ref,
                  w_step=pl.program_id(1), nw=nw, attn_softcap=attn_softcap,
                  scale=scale)


def _kv_block(W: int, wb: int) -> int:
    """KV block length: ``wb`` halved until it divides W, no lower than
    128 (the lane width the (1, Wb) position block needs); all of W when
    no such block divides it."""
    while W % wb and wb > 128:
        wb //= 2
    return wb if W % wb == 0 else W


def _specs(Hkv: int, rep: int, hd: int, kv_rows: int, kv_index, q_index):
    """Block specs shared by both kernels: q / out (Hkv, rep, hd) per
    request, k / v (kv_rows, Hkv, hd) per KV block, and the f32
    accumulator + running max / denominator scratch."""
    q_spec = pl.BlockSpec((None, Hkv, rep, hd), q_index)
    kv_spec = pl.BlockSpec((None, kv_rows, Hkv, hd), kv_index)
    scratch = [pltpu.VMEM((Hkv, rep, hd), jnp.float32),
               pltpu.VMEM((Hkv, rep, 1), jnp.float32),
               pltpu.VMEM((Hkv, rep, 1), jnp.float32)]
    return q_spec, kv_spec, scratch


@functools.partial(jax.jit,
                   static_argnames=("window", "attn_softcap", "wb", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_pos: jax.Array, pos: jax.Array, *,
                     window: int = 0, attn_softcap: float = 0.0,
                     wb: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, hd); caches (B, W, Hkv, hd); cache_pos (B, W); pos (B,).

    Returns (B, H, hd).  VMEM per step: 2*Wb*Hkv*hd (k,v) + H*hd acc —
    with Wb=512, Hkv=8, hd=128 in bf16: ~2 MB, so the 524k-long cache
    streams through in 1024 sequential blocks per request.
    """
    B, H, hd = q.shape
    _, W, Hkv, _ = k_cache.shape
    rep = H // Hkv
    wb = _kv_block(W, wb)
    grid = (B, W // wb)
    q_spec, kv_spec, scratch = _specs(
        Hkv, rep, hd, wb, kv_index=lambda b, w, p: (b, w, 0, 0),
        q_index=lambda b, w, p: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((None, 1, wb), lambda b, w, p: (b, 0, w))],
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_kernel, nw=grid[1], window=window,
                          attn_softcap=attn_softcap, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        interpret=interpret_mode(interpret),
        name="decode_attention",
    )(pos.astype(jnp.int32), q.reshape(B, Hkv, rep, hd), k_cache, v_cache,
      cache_pos.reshape(B, 1, W))
    return out.reshape(B, H, hd)


def _latent_kernel(pos_ref, q_ref, kv_ref, cpos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, nw: int, v_dim: int,
                   scale: float):
    """One (Wb, D) block of the latent cache for every head of a row:
    scores over all D columns, values from the first ``v_dim``."""
    w_step = pl.program_id(1)

    @pl.when(w_step == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    ok = _valid(cpos_ref[...], pos_ref[pl.program_id(0)], 0)   # (1, Wb)
    kv = kv_ref[...]                                           # (Wb, D)
    s = jax.lax.dot_general(q_ref[...], kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok, s, -1e30)                                # (H, Wb)
    m_old = m_ref[...]                                         # (H, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new) * ok.astype(jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(kv.dtype), kv_ref[:, :v_dim], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(w_step == nw - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("v_dim", "scale", "wb", "interpret"))
def mla_decode_attention(q: jax.Array, latent: jax.Array,
                         cache_pos: jax.Array, pos: jax.Array, *,
                         v_dim: int, scale: float, wb: int = 512,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Absorbed multi-head latent attention decode (``models.mla``).

    q: (B, H, D), the latent and rotary query of every head; latent:
    (B, W, D), one cache row shared by all heads (the latent, then the
    rotary key); cache_pos (B, W); pos (B,).  Returns (B, H, v_dim): the
    values are the latent's first ``v_dim`` columns, so each (Wb, D)
    block is read from HBM once per step for keys and values alike.
    Grid (B, W/Wb), KV length innermost, online softmax over (H, v_dim)
    f32 accumulators — ``models.attention.latent_decode_attention`` is
    the oracle.  With D = 576 and H = 128 a cached position costs 1152
    bytes and 2 * 128 * (576 + v_dim) flops, near the v5e's ridge point.
    """
    B, H, D = q.shape
    W = latent.shape[1]
    wb = _kv_block(W, wb)
    grid = (B, W // wb)
    row = pl.BlockSpec((None, H, D), lambda b, w, p: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[row,
                  pl.BlockSpec((None, wb, D), lambda b, w, p: (b, w, 0)),
                  pl.BlockSpec((None, 1, wb), lambda b, w, p: (b, 0, w))],
        out_specs=pl.BlockSpec((None, H, v_dim), lambda b, w, p: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, v_dim), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, nw=grid[1], v_dim=v_dim,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, v_dim), q.dtype),
        interpret=interpret_mode(interpret),
        name="mla_decode_attention",
    )(pos.astype(jnp.int32), q, latent, cache_pos.reshape(B, 1, W))


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, ppos_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, nw: int, window: int,
                  attn_softcap: float, scale: float):
    b = pl.program_id(0)
    w_step = pl.program_id(1)
    # an unmapped logical page (-1 in the block table) was DMA'd from
    # clipped page 0 — mask the whole block so its garbage never scores
    mapped = bt_ref[b, w_step] >= 0
    ok = mapped & _valid(ppos_ref[...], pos_ref[b], window)
    _attend_block(q_ref, k_ref, v_ref, ok, o_ref, acc_ref, m_ref, l_ref,
                  w_step=w_step, nw=nw, attn_softcap=attn_softcap,
                  scale=scale)


@functools.partial(jax.jit,
                   static_argnames=("window", "attn_softcap", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pos_pages: jax.Array,
                           block_table: jax.Array, pos: jax.Array, *,
                           window: int = 0, attn_softcap: float = 0.0,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Block-table-indexed flash decode over a paged KV pool.

    q: (B, H, hd); k_pages/v_pages: (P, ps, Hkv, hd); pos_pages: (P, ps);
    block_table: (B, n_logical) int32, -1 = unmapped; pos: (B,).
    Returns (B, H, hd).

    The block table rides in as a scalar-prefetch argument
    (``pltpu.PrefetchScalarGridSpec``), so each KV block's DMA source
    address is *computed from the table* in the BlockSpec index_map —
    the kernel streams exactly the pages a request owns straight out of
    the shared pool, with no dense gather materialized in HBM.  Grid is
    (B, n_logical) with the page dimension innermost, same online
    softmax and all-kv-head (ps, Hkv, hd) blocks as the contiguous
    kernel; unmapped pages (clipped to page 0 for the DMA) are masked out
    in-kernel via the prefetched table.
    """
    B, H, hd = q.shape
    P, ps, Hkv, _ = k_pages.shape
    n_logical = block_table.shape[1]
    rep = H // Hkv
    bt = jnp.asarray(block_table, jnp.int32)
    grid = (B, n_logical)

    def page_of(b, w, bt):
        # unmapped (-1) entries DMA page 0; the kernel masks them via
        # the same prefetched (unclipped) table
        return jnp.maximum(bt[b, w], 0)

    q_spec, kv_spec, scratch = _specs(
        Hkv, rep, hd, ps,
        kv_index=lambda b, w, bt, p: (page_of(b, w, bt), 0, 0, 0),
        q_index=lambda b, w, bt, p: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((None, 1, ps),
                               lambda b, w, bt, p: (page_of(b, w, bt), 0, 0))],
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, nw=n_logical, window=window,
                          attn_softcap=attn_softcap, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, hd), q.dtype),
        interpret=interpret_mode(interpret),
        name="paged_decode_attention",
    )(bt, pos.astype(jnp.int32), q.reshape(B, Hkv, rep, hd), k_pages,
      v_pages, pos_pages.reshape(P, 1, ps))
    return out.reshape(B, H, hd)
