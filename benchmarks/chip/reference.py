"""Plain float32 numerics that every model family's reference shares.

The family's own layers, weight draw and sizes live in
``references/<name>.py``, the module a configuration file names under
``"reference"`` (``spec.reference``).  This module keeps what no family
shapes: matmuls at ``precision="highest"`` and their float8 control, RMSNorm,
rotary positions, the router's tie margin, the vocabulary-chunked LM head,
the served-token gaps the check compares, and the weights' truncated-normal
draw.  It imports nothing of the program under test.

``lower=True`` rounds every matmul input to float8 (e4m3, one scale per
tensor or per row): the control, one precision step below the bfloat16 the
configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def served(config: dict, key: str, published):
    """What the program serves for ``key``: the served value of the file's
    ``departures`` entry where the program departs from the source, else
    the source's own value."""
    dep = config.get("departures", {}).get(key)
    return published if dep is None else dep["served"]


def _normal(key, shape, dtype, std=None):
    """Truncated normal at +-3 sigma, std = fan_in ** -0.5 by default."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = fan_in ** -0.5 if std is None else std
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
            * std).astype(dtype)


def _fp8(x, axis=None):
    """Round to float8 e4m3 with one scale per tensor (``axis=None``) or
    per slice along ``axis``, and back to float32."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, lower):
    """a @ w in float32 at full precision; ``lower`` rounds both inputs to
    float8 first (activations per row, the weight per tensor)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if lower:
        a, w = _fp8(a, axis=-1), _fp8(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """x: (T, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv                # (T, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _tie_margin(router, k):
    """How far the k-th largest router logit of each position lies above
    the (k+1)-th: how close the choice of experts is to a tie."""
    if k >= router.shape[-1]:
        return jnp.full(router.shape[:1], jnp.inf)
    vals, _ = jax.lax.top_k(router, k + 1)
    return vals[:, k - 1] - vals[:, k]


def _vocab_chunks(V: int) -> int:
    for n in (8, 4, 2):
        if V % n == 0:
            return n
    return 1


def head(h, lm_head, lower: bool):
    """(T, D) final hidden states -> (T, V) logits, one slice of the
    vocabulary at a time so that only that slice is widened to float32."""
    D, V = lm_head.shape
    n = _vocab_chunks(V)
    heads = lm_head.reshape(D, n, V // n).swapaxes(0, 1)
    out = jax.lax.map(lambda hw: _mm(h, hw, lower), heads)      # (n, T, V/n)
    return out.swapaxes(0, 1).reshape(h.shape[0], V)


@partial(jax.jit, static_argnums=(0, 2, 5))
def served_gaps(forward, w: dict, d, tokens: jax.Array, targets: jax.Array,
                control: bool = False):
    """Per position t of the family's ``forward(w, d, tokens, lower) ->
    (logits, tie margin)``: how far the reference's logit of
    ``targets[t]`` lies below the reference's best logit at t, and the
    reference's router tie margin at t.  With ``control`` also the gap of
    the token that the float8 pass puts first at t."""
    ref, margin = forward(w, d, tokens, False)
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, targets[:, None], 1)[:, 0]
    if not control:
        return gap, None, margin
    low_top = jnp.argmax(forward(w, d, tokens, True)[0], axis=-1)
    gap_low = best - jnp.take_along_axis(ref, low_top[:, None], 1)[:, 0]
    return gap, gap_low, margin
