"""Reference of the mixture-of-experts decoders with grouped-query
attention and a softmax top-k router: mixtral-8x22b and DBRX.

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
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.chip import reference
from benchmarks.chip.reference import (HIGHEST, _mm, _normal, _rms, _rope,
                                       _tie_margin, served)


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

    def layer_matmul_params(self) -> int:
        """Parameters one token multiplies through in one layer: the q, k,
        v and o projections, the router and its top-k experts' gated
        MLPs."""
        attn = self.d_model * self.head_dim * (2 * self.n_heads
                                               + 2 * self.n_kv_heads)
        router = self.d_model * self.n_experts
        experts = self.top_k * 3 * self.d_model * self.d_ff_expert
        return attn + router + experts

    def decode_token_flops(self, ctx: int) -> float:
        """One decode token attending over ``ctx`` positions, with its LM
        head: 2 flops per parameter multiplied, plus 4 * ctx * H * hd per
        layer for the scores and the weighted sum."""
        return (2.0 * (self.n_layers * self.layer_matmul_params()
                       + self.d_model * self.vocab)
                + 4.0 * ctx * self.n_heads * self.head_dim * self.n_layers)

    def prefill_flops(self, prompt_len: int) -> float:
        """A prompt of ``prompt_len`` tokens under causal attention (token
        i attends over i + 1 positions) and the LM head of its last
        token."""
        P = prompt_len
        attn_pairs = P * (P + 1) / 2.0
        return (2.0 * P * self.n_layers * self.layer_matmul_params()
                + 4.0 * attn_pairs * self.n_heads * self.head_dim
                * self.n_layers
                + 2.0 * self.d_model * self.vocab)

    def decode_attention_cost(self, ctxs, itemsize: int = 2):
        """(flops, bytes) of one layer's decode attention for rows that
        hold ``ctxs`` positions each: q and the output of every row, and
        the K and V of the positions each row holds."""
        rows = len(ctxs)
        held = float(sum(ctxs))
        flops = 4.0 * held * self.n_heads * self.head_dim
        qo = 2.0 * rows * self.n_heads * self.head_dim * itemsize
        kv = 2.0 * held * self.n_kv_heads * self.head_dim * itemsize
        return flops, qo + kv


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


SIZES = ("d_model", "n_heads", "n_kv_heads", "head_dim", "n_experts", "top_k",
         "d_ff_expert", "vocab", "n_layers", "rope_theta")


def program_sizes(model_cfg) -> dict:
    """The sizes the program serves, from its ``ModelConfig``."""
    return {"d_model": model_cfg.d_model, "n_heads": model_cfg.n_heads,
            "n_kv_heads": model_cfg.n_kv_heads,
            "head_dim": model_cfg.resolved_head_dim,
            "n_experts": model_cfg.moe.n_experts,
            "top_k": model_cfg.moe.top_k,
            "d_ff_expert": model_cfg.moe.d_ff_expert,
            "vocab": model_cfg.vocab, "n_layers": model_cfg.n_layers,
            "rope_theta": model_cfg.rope_theta}


def sizes(d: Dims) -> dict:
    """The same sizes, as the configuration file states them."""
    return {k: getattr(d, k) for k in SIZES}


# --------------------------------------------------------------------------
# weights: the serving launcher's draw, written out here
# --------------------------------------------------------------------------

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


@partial(jax.jit, static_argnums=(1, 3))
def forward(w: dict, d: Dims, tokens: jax.Array, lower: bool = False):
    """(T,) token ids -> (T, V) float32 logits of every position, and each
    position's smallest router tie margin over the layers."""
    x = w["embed"][tokens].astype(jnp.float32)
    x, margins = jax.lax.scan(lambda x, lw: _layer(x, lw, d, lower),
                              x, w["layers"])
    h = _rms(x, w["final_norm"], d.rms_norm_eps)
    return reference.head(h, w["lm_head"], lower), jnp.min(margins, axis=0)
