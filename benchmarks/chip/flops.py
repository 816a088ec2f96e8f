"""Operations and bytes the algorithm needs, from shapes.

These count the work a token needs, not what the program computes: the
top-k experts a token is routed to, not a padded expert capacity; the
positions a row holds, not the cache's width.  A roofline share above
100 % therefore means the count is wrong, or the time misses work.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    """The sizes of a mixture-of-experts decoder that the counts need."""
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    vocab: int
    n_layers: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e4


def layer_matmul_params(d: Dims) -> int:
    """Parameters one token multiplies through in one layer: the q, k, v
    and o projections, the router and its top-k experts' gated MLPs."""
    attn = d.d_model * d.head_dim * (2 * d.n_heads + 2 * d.n_kv_heads)
    router = d.d_model * d.n_experts
    experts = d.top_k * 3 * d.d_model * d.d_ff_expert
    return attn + router + experts


def decode_token_flops(d: Dims, ctx: int) -> float:
    """One decode token attending over ``ctx`` positions, with its LM
    head: 2 flops per parameter multiplied, plus 4 * ctx * H * hd per
    layer for the scores and the weighted sum."""
    return (2.0 * (d.n_layers * layer_matmul_params(d) + d.d_model * d.vocab)
            + 4.0 * ctx * d.n_heads * d.head_dim * d.n_layers)


def prefill_flops(d: Dims, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens under causal attention (token i
    attends over i + 1 positions) and the LM head of its last token."""
    P = prompt_len
    attn_pairs = P * (P + 1) / 2.0
    return (2.0 * P * d.n_layers * layer_matmul_params(d)
            + 4.0 * attn_pairs * d.n_heads * d.head_dim * d.n_layers
            + 2.0 * d.d_model * d.vocab)


def decode_attention_cost(d: Dims, ctxs, itemsize: int = 2):
    """(flops, bytes) of one layer's decode attention for rows that hold
    ``ctxs`` positions each: q and the output of every row, and the K and
    V of the positions each row holds."""
    rows = len(ctxs)
    held = float(sum(ctxs))
    flops = 4.0 * held * d.n_heads * d.head_dim
    qo = 2.0 * rows * d.n_heads * d.head_dim * itemsize
    kv = 2.0 * held * d.n_kv_heads * d.head_dim * itemsize
    return flops, qo + kv


def roofline_time(flops: float, nbytes: float, peaks: dict):
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
