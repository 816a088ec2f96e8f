"""Reference of DeepSeek-V3's decoder as one chip serves its share:
multi-head latent attention, leading dense layers, then MoE layers with
group-limited sigmoid routing over all of the router's experts, of which
the chip holds a few.

A full forward pass over one sequence in ``jax.numpy``: token embedding;
per layer RMSNorm and MLA, expanded (per head, keys and values from the
latent; q . k over the no-rope and rope dimensions at YaRN's softmax
scale; YaRN rotary frequencies on the rope dimensions, both halves
rotated as the program does), then RMSNorm and either the dense SwiGLU
or the MoE: sigmoid scores, experts chosen on scores plus the correction
bias inside the best ``topk_group`` of ``n_group`` groups (a group's
score is its two best), weighted by their unbiased scores normalised to
sum 1 times ``routed_scaling_factor``.  Only the held experts' part of
the routed result is computed, as on the chip; what the other chips'
experts add is left out here too.  The ungated shared expert is added.
Final RMSNorm and the LM head over the vocabulary slice.  No kernels, no
cache, no batching, every matmul at ``precision="highest"``.  It imports
nothing of the program under test.

The weights are drawn from the seed by the launcher's recipe
(``init_weights``), kept in the dtype they are served in, and widened
one expert, one group of heads or one slice of the vocabulary at a time.

Counts (``Dims``), per token, with A = the attention matmuls' parameters
(W_qa, W_qb, W_kva, W_kvb — as W_uk and W_uv when absorbed — and W_o:
187.1 M at DeepSeek-V3's widths), R = the router (d x 256), S = the
shared expert, X = one routed expert (3 d 2048 each), F = the dense FFN
and V = the LM head (d x vocabulary):

- ``decode_token_flops(ctx)`` = 2 (L A + L_moe (R + S + top_k held / E X)
  + L_dense F + V) + 2 H (D + kv_lora_rank) ctx L, with D = kv_lora_rank +
  rope = 576: the absorbed attention's scores over 576 values and its
  weighted sum over 512, 278,528 flops a held position and layer.  A
  token meets top_k held / E = 8 x 8 / 256 = 0.25 held experts a layer on
  average.
- ``prefill_flops(P)`` = 2 P (the same matmuls) + 2 H (qk + v) P (P+1)/2 L
  for the expanded causal attention (qk = 192, v = 128), and the LM head
  of the last token.
- ``decode_attention_cost(ctxs)``: one layer's absorbed attention for rows
  holding ``ctxs`` positions: 278,528 flops a held position; bytes of
  each row's q (H x 576) and output (H x 512) and 1152 bytes (576 values)
  a held position.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.reference import HIGHEST, _mm, _normal, _rms, served

HEAD_GROUP = 16          # heads widened together in the expanded attention


@dataclass(frozen=True)
class Dims:
    """The sizes of a DeepSeek-V3 share that the counts and the pass
    need."""
    d_model: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_layers: int
    n_dense_layers: int
    d_ff_dense: int
    n_experts: int          # the router's outputs
    n_held: int             # routed experts held here
    first_held: int
    top_k: int
    n_group: int
    topk_group: int
    d_ff_expert: int
    n_shared_experts: int
    routed_scaling: float
    vocab: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e4
    yarn_factor: float = 40.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def attn_params(self) -> int:
        """W_qa, W_qb, W_kva, W_kvb and W_o: the attention matmuls one token
        multiplies through, expanded or absorbed alike."""
        D, H = self.d_model, self.n_heads
        return (D * self.q_lora_rank + self.q_lora_rank * H * self.qk_head_dim
                + D * self.latent_dim
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * D)

    def token_matmul_params(self) -> float:
        """Parameters one token multiplies through below the LM head."""
        D = self.d_model
        expert = 3 * D * self.d_ff_expert
        moe = (D * self.n_experts + self.n_shared_experts * expert
               + self.top_k * self.n_held / self.n_experts * expert)
        return (self.n_layers * self.attn_params()
                + self.n_moe_layers * moe
                + self.n_dense_layers * 3 * D * self.d_ff_dense)

    def decode_attention_flops(self) -> int:
        """Flops of one held position in one layer's absorbed attention."""
        return 2 * self.n_heads * (self.latent_dim + self.kv_lora_rank)

    def decode_token_flops(self, ctx: int) -> float:
        return (2.0 * (self.token_matmul_params() + self.d_model * self.vocab)
                + float(self.decode_attention_flops()) * ctx * self.n_layers)

    def prefill_flops(self, prompt_len: int) -> float:
        P = prompt_len
        pairs = P * (P + 1) / 2.0
        return (2.0 * P * self.token_matmul_params()
                + 2.0 * self.n_heads * (self.qk_head_dim + self.v_head_dim)
                * pairs * self.n_layers
                + 2.0 * self.d_model * self.vocab)

    def decode_attention_cost(self, ctxs, itemsize: int = 2):
        rows = len(ctxs)
        held = float(sum(ctxs))
        flops = self.decode_attention_flops() * held
        qo = rows * self.n_heads * (self.latent_dim + self.kv_lora_rank) \
            * itemsize
        return flops, qo + held * self.latent_dim * itemsize


def dims_of(config: dict) -> Dims:
    """The sizes of a configuration file in DeepSeek-V3's keys, with the
    values the program serves."""
    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("hidden_act", "silu")):
        if config[key] != want:
            raise ValueError(f"{key} {config[key]!r}: this reference "
                             f"serves {want!r}")
    y, share = config["rope_scaling"], config["expert_share"]
    if y["type"] != "yarn":
        raise ValueError(f"rope_scaling type {y['type']!r}")
    per_group = share["router_experts"] // config["n_group"]
    first, n = share["first_held"], config["n_routed_experts"]
    if first // per_group != (first + n - 1) // per_group:
        raise ValueError(f"held experts {first}..{first + n - 1} span "
                         f"groups of {per_group}; the tie margin takes one")
    return Dims(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_layers=config["num_hidden_layers"],
        n_dense_layers=config["first_k_dense_replace"],
        d_ff_dense=config["intermediate_size"],
        n_experts=share["router_experts"], n_held=config["n_routed_experts"],
        first_held=share["first_held"],
        top_k=config["num_experts_per_tok"], n_group=config["n_group"],
        topk_group=config["topk_group"],
        d_ff_expert=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling=float(config["routed_scaling_factor"]),
        vocab=config["vocab_size"],
        rms_norm_eps=served(config, "rms_norm_eps", config["rms_norm_eps"]),
        rope_theta=float(served(config, "rope_theta", config["rope_theta"])),
        yarn_factor=float(y["factor"]),
        yarn_original_max_position=int(y["original_max_position_embeddings"]),
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]),
        yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]))


SIZES = ("d_model", "n_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_layers",
         "n_dense_layers", "d_ff_dense", "n_experts", "n_held", "first_held",
         "top_k", "n_group", "topk_group", "d_ff_expert", "n_shared_experts",
         "shared_gated", "scoring", "routed_scaling", "vocab", "rope_theta",
         "yarn")


def program_sizes(model_cfg) -> dict:
    """The sizes the program serves, from its ``ModelConfig``."""
    a, m, y = model_cfg.mla, model_cfg.moe, model_cfg.rope_scaling
    return {
        "d_model": model_cfg.d_model, "n_heads": model_cfg.n_heads,
        "q_lora_rank": a.q_lora_rank, "kv_lora_rank": a.kv_lora_rank,
        "qk_nope_head_dim": a.qk_nope_head_dim,
        "qk_rope_head_dim": a.qk_rope_head_dim, "v_head_dim": a.v_head_dim,
        "n_layers": model_cfg.n_layers,
        "n_dense_layers": model_cfg.layer_kinds.count("mla_dense"),
        "d_ff_dense": model_cfg.d_ff, "n_experts": m.n_experts,
        "n_held": m.held, "first_held": m.first_held, "top_k": m.top_k,
        "n_group": m.n_group, "topk_group": m.topk_group,
        "d_ff_expert": m.d_ff_expert,
        "n_shared_experts": m.n_shared_experts
        if m.d_ff_shared == m.d_ff_expert else (m.n_shared_experts,
                                                m.d_ff_shared),
        "shared_gated": m.shared_gated, "scoring": m.scoring,
        "routed_scaling": m.routed_scaling, "vocab": model_cfg.vocab,
        "rope_theta": model_cfg.rope_theta,
        "yarn": (y.factor, y.original_max_position, y.beta_fast,
                 y.beta_slow, y.mscale, y.mscale_all_dim)}


def sizes(d: Dims) -> dict:
    """The same sizes, as the configuration file states them."""
    out = {k: getattr(d, k) for k in SIZES
           if k not in ("shared_gated", "scoring", "yarn")}
    out.update(shared_gated=False, scoring="sigmoid",
               yarn=(d.yarn_factor, d.yarn_original_max_position,
                     d.yarn_beta_fast, d.yarn_beta_slow, d.yarn_mscale,
                     d.yarn_mscale_all_dim))
    return out


# --------------------------------------------------------------------------
# weights: the serving launcher's draw, written out here
# --------------------------------------------------------------------------

def _split(key, n):
    return list(jax.random.split(key, n))


def _layer_weights(key, d: Dims, dtype, dense: bool) -> dict:
    D, H, L = d.d_model, d.n_heads, d.kv_lora_rank
    ks = _split(key, 6)
    ka = _split(ks[0], 5)
    w = {
        "ln1": jnp.zeros((D,), dtype), "ln2": jnp.zeros((D,), dtype),
        "wq_a": _normal(ka[0], (D, d.q_lora_rank), dtype),
        "q_norm": jnp.zeros((d.q_lora_rank,), dtype),
        "wq_b": _normal(ka[1], (d.q_lora_rank, H * d.qk_head_dim), dtype),
        "wkv_a": _normal(ka[2], (D, d.latent_dim), dtype),
        "kv_norm": jnp.zeros((L,), dtype),
        "wkv_b": _normal(ka[3], (L, H * (d.qk_nope_head_dim + d.v_head_dim)),
                         dtype),
        "wo": _normal(ka[4], (H * d.v_head_dim, D), dtype),
    }
    if dense:
        F = d.d_ff_dense
        w.update(w1=_normal(jax.random.fold_in(ks[1], 1), (D, F), dtype),
                 w3=_normal(jax.random.fold_in(ks[1], 2), (D, F), dtype),
                 w2=_normal(jax.random.fold_in(ks[1], 3), (F, D), dtype))
        return w
    kf = _split(ks[1], 12)
    E, F, S = d.n_held, d.d_ff_expert, d.n_shared_experts * d.d_ff_expert
    w.update(
        router=_normal(kf[0], (D, d.n_experts), jnp.float32),
        we1=_normal(kf[1], (E, D, F), dtype),
        we3=_normal(kf[2], (E, D, F), dtype),
        we2=_normal(kf[3], (E, F, D), dtype),
        ws1=_normal(kf[4], (D, S), dtype),
        ws3=_normal(kf[5], (D, S), dtype),
        ws2=_normal(kf[6], (S, D), dtype),
        score_bias=0.01 * jax.random.normal(kf[11], (d.n_experts,),
                                            jnp.float32))
    return w


@partial(jax.jit, static_argnums=(0, 2))
def _init(d: Dims, key, dtype):
    keys = _split(key, 6)
    n_blocks = d.n_layers - d.n_dense_layers
    block_keys = jnp.stack(_split(jax.random.fold_in(keys[2], 0), n_blocks))
    return {
        "embed": _normal(keys[0], (d.vocab, d.d_model), dtype, std=0.02),
        "final_norm": jnp.zeros((d.d_model,), dtype),
        "lm_head": _normal(keys[1], (d.d_model, d.vocab), dtype),
        "dense": [_layer_weights(jax.random.fold_in(keys[5], i), d, dtype,
                                 True) for i in range(d.n_dense_layers)],
        "moe": jax.vmap(lambda k: _layer_weights(k, d, dtype, False))(
            block_keys),
    }


def init_weights(d: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """The weights the serving launcher draws for ``seed``, on the device
    in one jitted program.  Norm weights are stored as offsets from 1."""
    return _init(d, jax.random.PRNGKey(seed), jnp.dtype(dtype))


# --------------------------------------------------------------------------
# forward pass
# --------------------------------------------------------------------------

def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(d: Dims) -> np.ndarray:
    """YaRN's frequencies, as HF's ``DeepseekV3YarnRotaryEmbedding`` builds
    them: the base frequency where a dimension turns more than
    ``beta_fast`` times in the original context, the base over ``factor``
    where it turns fewer than ``beta_slow`` times, a linear ramp
    between."""
    dim, base = d.qk_rope_head_dim, d.rope_theta
    exps = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    extra = np.float32(1.0) / np.float32(base) ** exps
    inter = np.float32(1.0) / (np.float32(d.yarn_factor)
                               * np.float32(base) ** exps)

    def corr(rot):
        return (dim * math.log(d.yarn_original_max_position
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(corr(d.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(d.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0.0, 1.0)
    extra_share = np.float32(1.0) - ramp
    return (inter * (1 - extra_share) + extra * extra_share).astype(
        np.float32)


def softmax_scale(d: Dims) -> float:
    m = _yarn_mscale(d.yarn_factor, d.yarn_mscale_all_dim) \
        if d.yarn_mscale_all_dim else 1.0
    return d.qk_head_dim ** -0.5 * m * m


def _rotate(x, pos, d: Dims):
    """x: (T, heads, rope); both halves of the rope dimensions rotated."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(rope_frequencies(d))
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out * (_yarn_mscale(d.yarn_factor, d.yarn_mscale)
                  / _yarn_mscale(d.yarn_factor, d.yarn_mscale_all_dim))


def _attention(x, lw, d: Dims, lower: bool):
    T = x.shape[0]
    H, N, L = d.n_heads, d.qk_nope_head_dim, d.kv_lora_rank
    pos = jnp.arange(T)
    h = _rms(x, lw["ln1"], d.rms_norm_eps)
    c_q = _rms(_mm(h, lw["wq_a"], lower), lw["q_norm"], d.rms_norm_eps)
    q = _mm(c_q, lw["wq_b"], lower).reshape(T, H, d.qk_head_dim)
    q_nope, q_pe = q[..., :N], _rotate(q[..., N:], pos, d)
    kv = _mm(h, lw["wkv_a"], lower)
    c_kv = _rms(kv[:, :L], lw["kv_norm"], d.rms_norm_eps)
    k_pe = _rotate(kv[:, None, L:], pos, d)[:, 0]                # (T, rope)
    causal = pos[None, :] <= pos[:, None]
    G = math.gcd(HEAD_GROUP, H)
    w_b = lw["wkv_b"].reshape(L, H // G, G, N + d.v_head_dim).swapaxes(0, 1)

    def heads(args):                    # G heads: their keys and values
        qn, qp, wb = args               # (T, G, N), (T, G, rope), (L, G, N+v)
        kvh = _mm(c_kv, wb.reshape(L, -1), lower).reshape(T, G, -1)
        s = (jnp.einsum("qgn,kgn->gqk", qn, kvh[..., :N], precision=HIGHEST)
             + jnp.einsum("qgr,kr->gqk", qp, k_pe, precision=HIGHEST))
        p = jax.nn.softmax(jnp.where(causal, s * softmax_scale(d), -jnp.inf),
                           -1)
        return jnp.einsum("gqk,kgv->qgv", p, kvh[..., N:], precision=HIGHEST)

    o = jax.lax.map(heads, (q_nope.reshape(T, H // G, G, N).swapaxes(0, 1),
                            q_pe.reshape(T, H // G, G, -1).swapaxes(0, 1),
                            w_b))                         # (H/G, T, G, v)
    o = o.swapaxes(0, 1).reshape(T, H * d.v_head_dim)
    return x + _mm(o, lw["wo"], lower)


def _swiglu(h, w1, w3, w2, lower):
    return _mm(jax.nn.silu(_mm(h, w1, lower)) * _mm(h, w3, lower), w2, lower)


def _kept_groups(choice, d: Dims):
    """(T, G) group scores (sum of each group's two best) and the (T, G)
    mask of the ``topk_group`` groups kept."""
    T = choice.shape[0]
    G = d.n_group
    grouped = jnp.sort(choice.reshape(T, G, -1), axis=-1)
    score = grouped[..., -1] + grouped[..., -2]
    order = jnp.argsort(-score, axis=-1)
    kept = jnp.zeros((T, G), bool).at[
        jnp.arange(T)[:, None], order[:, :d.topk_group]].set(True)
    return score, kept, order


def _chosen(choice, kept, d: Dims):
    """(T, E) mask of the ``top_k`` best experts inside the kept groups."""
    E = choice.shape[1]
    cand = jnp.where(jnp.repeat(kept, E // d.n_group, axis=-1), choice,
                     -jnp.inf)
    order = jnp.argsort(-cand, axis=-1)
    chosen = jnp.zeros(choice.shape, bool).at[
        jnp.arange(choice.shape[0])[:, None], order[:, :d.top_k]].set(True)
    return chosen, jnp.take_along_axis(cand, order[:, d.top_k - 1:d.top_k + 1],
                                       axis=-1)


def route(logits, bias, d: Dims):
    """The held experts' routing weights (T, n_held) from the router's
    (T, E) logits, and the tie margin of the held experts' choice: the
    least change of the selection scores that changes which held experts
    a token gets, in score units.  Three flips count: a held expert
    crossing the k-th/(k+1)-th boundary inside the kept groups; the group
    that holds them leaving or joining the kept groups; the last kept and
    the first dropped group trading places.  A group move counts only
    where it changes the held experts chosen: ties among experts held on
    other chips move nothing here."""
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias
    gscore, kept, gorder = _kept_groups(choice, d)
    chosen, bounds = _chosen(choice, kept, d)
    last, nxt = bounds[:, 0], bounds[:, 1]        # the k-th and (k+1)-th
    w = jnp.where(chosen, scores, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * d.routed_scaling
    held = slice(d.first_held, d.first_held + d.n_held)
    T, E = choice.shape
    g = d.first_held // (E // d.n_group)          # the group holding them
    rows, k = jnp.arange(T), d.topk_group
    h_chosen, in_kept = chosen[:, held], kept[:, g]
    expert = jnp.min(jnp.where(h_chosen, choice[:, held] - nxt[:, None],
                               last[:, None] - choice[:, held]), axis=-1)
    expert = jnp.where(in_kept, expert, jnp.inf)
    g_sorted = jnp.take_along_axis(gscore, gorder, axis=-1)
    g_in, g_out = g_sorted[:, k - 1], g_sorted[:, k]

    def changes(alt_kept):
        alt = _chosen(choice, alt_kept, d)[0][:, held]
        return jnp.any(alt != h_chosen, axis=-1)

    moved = jnp.where(
        in_kept[:, None],
        kept.at[rows, g].set(False).at[rows, gorder[:, k]].set(True),
        kept.at[rows, g].set(True).at[rows, gorder[:, k - 1]].set(False))
    group = jnp.where(changes(moved),
                      jnp.where(in_kept, gscore[:, g] - g_out,
                                g_in - gscore[:, g]), jnp.inf)
    swapped = kept.at[rows, gorder[:, k - 1]].set(False) \
        .at[rows, gorder[:, k]].set(True)
    swap = jnp.where(changes(swapped), g_in - g_out, jnp.inf)
    return w[:, held], jnp.minimum(jnp.minimum(expert, group), swap)


def _moe(x, lw, d: Dims, lower: bool):
    h = _rms(x, lw["ln2"], d.rms_norm_eps)
    gate, margin = route(_mm(h, lw["router"], lower), lw["score_bias"], d)

    def expert(y, xs):
        w1, w3, w2, g = xs
        return y + g[:, None] * _swiglu(h, w1, w3, w2, lower), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (lw["we1"], lw["we3"], lw["we2"], gate.T))
    shared = _swiglu(h, lw["ws1"], lw["ws3"], lw["ws2"], lower)
    return x + y + shared, margin


def _dense(x, lw, d: Dims, lower: bool):
    h = _rms(x, lw["ln2"], d.rms_norm_eps)
    return x + _swiglu(h, lw["w1"], lw["w3"], lw["w2"], lower)


@partial(jax.jit, static_argnums=(1, 3))
def forward(w: dict, d: Dims, tokens: jax.Array, lower: bool = False):
    """(T,) token ids -> (T, V) float32 logits of every position, and each
    position's smallest tie margin of the held experts' choice over the
    MoE layers."""
    x = w["embed"][tokens].astype(jnp.float32)
    for lw in w["dense"]:
        x = _dense(_attention(x, lw, d, lower), lw, d, lower)

    def layer(x, lw):
        return _moe(_attention(x, lw, d, lower), lw, d, lower)

    x, margins = jax.lax.scan(layer, x, w["moe"])
    h = _rms(x, w["final_norm"], d.rms_norm_eps)
    return reference.head(h, w["lm_head"], lower), jnp.min(margins, axis=0)
