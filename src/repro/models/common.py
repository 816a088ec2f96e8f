"""Shared model building blocks: norms, RoPE and its YaRN scaling,
softcap, init helpers."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in f32 accumulation, cast back to input dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's rotary frequencies (HF ``DeepseekV3YarnRotaryEmbedding``):
    the base frequencies where a dimension turns more than ``beta_fast``
    times over the original context, the base divided by ``factor``
    where it turns fewer than ``beta_slow`` times, a linear ramp between.
    ``yarn``: a ``config.YarnConfig``.  Returns (dim/2,) float32."""
    exps = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    extra = np.float32(1.0) / np.float32(theta) ** exps
    inter = np.float32(1.0) / (np.float32(yarn.factor)
                               * np.float32(theta) ** exps)

    def turns_dim(rotations: float) -> float:
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0.0, 1.0)
    keep = np.float32(1.0) - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def yarn_rope_scale(yarn) -> float:
    """The factor YaRN puts on cos and sin."""
    return (_yarn_mscale(yarn.factor, yarn.mscale)
            / _yarn_mscale(yarn.factor, yarn.mscale_all_dim))


def yarn_softmax_scale(head_dim: int, yarn) -> float:
    """head_dim ** -0.5, times YaRN's attention factor squared where the
    configuration sets ``mscale_all_dim``."""
    scale = head_dim ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        m = _yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        scale *= m * m
    return scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs=None) -> jax.Array:
    """Rotary position embedding.

    x: (..., seq, n_heads, head_dim), positions: (..., seq) int32.
    ``freqs``: (head_dim/2,) frequencies in place of ``theta``'s own
    (YaRN's, for instance).
    """
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(head_dim, theta)             # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    sin = jnp.sin(angles)[..., None, :]                       # (..., seq, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def activation(x: jax.Array, act: str) -> jax.Array:
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(act)


def dense_init(key: jax.Array, shape, dtype, scale: float | None = None) -> jax.Array:
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) * std).astype(dtype)


def split_keys(key: jax.Array, n: int):
    return list(jax.random.split(key, n))


def causal_mask_bias(q_pos: jax.Array, k_pos: jax.Array, window: int = 0) -> jax.Array:
    """Additive attention bias: 0 where k may be attended from q, -inf otherwise.

    q_pos: (..., Sq), k_pos: (..., Sk). window>0 limits to a sliding window
    (k > q - window).
    """
    ok = k_pos[..., None, :] <= q_pos[..., :, None]
    if window > 0:
        ok &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
