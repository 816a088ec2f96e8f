"""Plain float32 reference of the mixture-of-experts decoders the cells run.

A full forward pass over one sequence in ``jax.numpy``: token embedding;
per layer RMSNorm, grouped-query attention with rotary positions and a
causal mask, RMSNorm, a softmax router whose top-k probabilities are
renormalised, and the routed experts' SwiGLU MLPs; final RMSNorm and the
LM head.  No kernels, no cache, no batching, every matmul at
``precision="highest"``.  It imports nothing of the program under test.

The weights are drawn from the seed by the benchmark's own recipe
(``init_weights``), the same draw the serving launcher makes, so the
reference takes no weights from the program.  They are kept in the
dtype they are served in (every bfloat16 value is exact in float32) and
widened one expert, or one slice of the vocabulary, at a time, so the
pass fits beside them on one chip.

``lower=True`` computes the same pass with every matmul input rounded to
float8 (e4m3, one scale per tensor or per row): the control, one
precision step below the bfloat16 the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.chip.flops import Dims

HIGHEST = jax.lax.Precision.HIGHEST


def served(config: dict, key: str, published):
    """What the program serves for ``key``: the served value of the file's
    ``departures`` entry where the program departs from the source, else
    the source's own value."""
    dep = config.get("departures", {}).get(key)
    return published if dep is None else dep["served"]


def dims_of(config: dict) -> Dims:
    """The sizes of a configuration file, in the keys of its source, with
    the values the program serves."""
    if "ffn_config" in config:          # DBRX's own config.json keys
        attn, ffn = config["attn_config"], config["ffn_config"]
        return Dims(d_model=config["d_model"], n_heads=config["n_heads"],
                    n_kv_heads=attn["kv_n_heads"],
                    head_dim=config["d_model"] // config["n_heads"],
                    n_experts=ffn["moe_num_experts"], top_k=ffn["moe_top_k"],
                    d_ff_expert=ffn["ffn_hidden_size"],
                    vocab=config["vocab_size"], n_layers=config["n_layers"],
                    rms_norm_eps=served(config, "norm_eps", None),
                    rope_theta=served(config, "attn_config.rope_theta",
                                      attn["rope_theta"]))
    return Dims(d_model=config["hidden_size"],
                n_heads=config["num_attention_heads"],
                n_kv_heads=config["num_key_value_heads"],
                head_dim=config.get("head_dim", config["hidden_size"]
                                    // config["num_attention_heads"]),
                n_experts=config["num_local_experts"],
                top_k=config["num_experts_per_tok"],
                d_ff_expert=config["intermediate_size"],
                vocab=config["vocab_size"],
                n_layers=config["num_hidden_layers"],
                rms_norm_eps=served(config, "rms_norm_eps",
                                    config["rms_norm_eps"]),
                rope_theta=served(config, "rope_theta", config["rope_theta"]))


# --------------------------------------------------------------------------
# weights: the serving launcher's draw, written out here
# --------------------------------------------------------------------------

def _normal(key, shape, dtype, std=None):
    """Truncated normal at +-3 sigma, std = fan_in ** -0.5 by default."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = fan_in ** -0.5 if std is None else std
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
            * std).astype(dtype)


def _layer_weights(key, d: Dims, dtype) -> dict:
    D, hd = d.d_model, d.head_dim
    ks = list(jax.random.split(key, 6))
    ka = list(jax.random.split(ks[0], 4))
    kf = list(jax.random.split(ks[1], 12))
    E, F = d.n_experts, d.d_ff_expert
    return {
        "ln1": jnp.zeros((D,), dtype), "ln2": jnp.zeros((D,), dtype),
        "wq": _normal(ka[0], (D, d.n_heads * hd), dtype),
        "wk": _normal(ka[1], (D, d.n_kv_heads * hd), dtype),
        "wv": _normal(ka[2], (D, d.n_kv_heads * hd), dtype),
        "wo": _normal(ka[3], (d.n_heads * hd, D), dtype),
        "router": _normal(kf[0], (D, E), jnp.float32),
        "we1": _normal(kf[1], (E, D, F), dtype),
        "we3": _normal(kf[2], (E, D, F), dtype),
        "we2": _normal(kf[3], (E, F, D), dtype),
    }


@partial(jax.jit, static_argnums=(0, 2))
def _init(d: Dims, key, dtype):
    keys = list(jax.random.split(key, 6))
    layer_keys = jnp.stack(list(jax.random.split(
        jax.random.fold_in(keys[2], 0), d.n_layers)))
    return {
        "embed": _normal(keys[0], (d.vocab, d.d_model), dtype, std=0.02),
        "final_norm": jnp.zeros((d.d_model,), dtype),
        "lm_head": _normal(keys[1], (d.d_model, d.vocab), dtype),
        "layers": jax.vmap(lambda k: _layer_weights(k, d, dtype))(layer_keys),
    }


def init_weights(d: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """The weights the serving launcher draws for ``seed``, on the device
    in one jitted program.  Norm weights are stored as offsets from 1."""
    return _init(d, jax.random.PRNGKey(seed), jnp.dtype(dtype))


# --------------------------------------------------------------------------
# forward pass
# --------------------------------------------------------------------------

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


def _layer(x, lw, d: Dims, lower: bool):
    T = x.shape[0]
    H, Hkv, hd = d.n_heads, d.n_kv_heads, d.head_dim
    pos = jnp.arange(T)
    h = _rms(x, lw["ln1"], d.rms_norm_eps)
    q = _rope(_mm(h, lw["wq"], lower).reshape(T, H, hd), pos, d.rope_theta)
    k = _rope(_mm(h, lw["wk"], lower).reshape(T, Hkv, hd), pos, d.rope_theta)
    v = _mm(h, lw["wv"], lower).reshape(T, Hkv, hd)
    causal = pos[None, :] <= pos[:, None]                       # (T, T)

    def group(qkv):                     # one kv head and its query heads
        qg, kg, vg = qkv                # (T, rep, hd), (T, hd), (T, hd)
        s = jnp.einsum("qrd,kd->rqk", qg, kg, precision=HIGHEST)
        p = jax.nn.softmax(jnp.where(causal, s * hd ** -0.5, -jnp.inf), -1)
        return jnp.einsum("rqk,kd->qrd", p, vg, precision=HIGHEST)

    o = jax.lax.map(group, (q.reshape(T, Hkv, H // Hkv, hd).swapaxes(0, 1),
                            k.swapaxes(0, 1), v.swapaxes(0, 1)))
    o = o.swapaxes(0, 1).reshape(T, H * hd)                     # (T, H*hd)
    x = x + _mm(o, lw["wo"], lower)

    h = _rms(x, lw["ln2"], d.rms_norm_eps)
    router = _mm(h, lw["router"], lower)                         # (T, E)
    probs = jax.nn.softmax(router, axis=-1)
    top, idx = jax.lax.top_k(probs, d.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, d.n_experts) * top[..., None], 1)

    def expert(y, xs):
        w1, w3, w2, g = xs
        a = jax.nn.silu(_mm(h, w1, lower)) * _mm(h, w3, lower)
        return y + g[:, None] * _mm(a, w2, lower), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (lw["we1"], lw["we3"], lw["we2"], gate.T))
    return x + y, _tie_margin(router, d.top_k)


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


@partial(jax.jit, static_argnums=(1, 3))
def forward(w: dict, d: Dims, tokens: jax.Array, lower: bool = False):
    """(T,) token ids -> (T, V) float32 logits of every position, and each
    position's smallest router tie margin over the layers."""
    x = w["embed"][tokens].astype(jnp.float32)
    x, margins = jax.lax.scan(lambda x, lw: _layer(x, lw, d, lower),
                              x, w["layers"])
    h = _rms(x, w["final_norm"], d.rms_norm_eps)
    n = _vocab_chunks(d.vocab)
    heads = w["lm_head"].reshape(d.d_model, n, d.vocab // n).swapaxes(0, 1)
    out = jax.lax.map(lambda hw: _mm(h, hw, lower), heads)      # (n, T, V/n)
    return (out.swapaxes(0, 1).reshape(tokens.shape[0], d.vocab),
            jnp.min(margins, axis=0))


def logits(w: dict, d: Dims, tokens: jax.Array, lower: bool = False):
    return forward(w, d, tokens, lower)[0]


@partial(jax.jit, static_argnums=(1, 4))
def served_gaps(w: dict, d: Dims, tokens: jax.Array, targets: jax.Array,
                control: bool = False):
    """Per position t: how far the reference's logit of ``targets[t]``
    lies below the reference's best logit at t, and the reference's router
    tie margin at t.  With ``control`` also the gap of the token that the
    float8 pass puts first at t."""
    ref, margin = forward(w, d, tokens, False)
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, targets[:, None], 1)[:, 0]
    if not control:
        return gap, None, margin
    low_top = jnp.argmax(logits(w, d, tokens, True), axis=-1)
    gap_low = best - jnp.take_along_axis(ref, low_top[:, None], 1)[:, 0]
    return gap, gap_low, margin
