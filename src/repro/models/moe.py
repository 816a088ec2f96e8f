"""Mixture-of-experts FFN layer.

The *baseline* (paper-faithful "existing system") dispatch is the
scatter/gather capacity-buffer formulation used by monolithic-SPMD
serving systems: every token is placed into a per-expert capacity slot,
experts run dense GEMMs over their buffers, and results are combined by a
scatter-add.  Under pjit this lowers to XLA-inserted all-gathers of the
token activations — the generic-collective cost the paper attributes to
NCCL-style all-to-all serving.

The *optimized* M2N dispatch (the paper's contribution, adapted to TPU)
lives in ``repro.core.m2n`` and moves exactly the routed tokens between
attention and expert shards with ``shard_map`` collectives.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import MoEConfig
from repro.models.common import activation
from repro.models.ffn import gated_ffn


class Routing(NamedTuple):
    """Routing decision for a flat batch of T tokens."""
    gates: jax.Array        # (T, K) combine weights (f32)
    experts: jax.Array      # (T, K) int32 expert ids
    probs: jax.Array        # (T, E) full router probabilities (f32)


def route(x: jax.Array, w_router: jax.Array, top_k: int,
          bias: jax.Array | None = None) -> Routing:
    """Top-k softmax routing.  x: (T, d), w_router: (d, E).

    bias: optional (E,) additive logit bias (DeepSeek-style router bias;
    also how the serving benchmarks induce a controlled routing skew).
    """
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return Routing(gates, experts.astype(jnp.int32), probs)


def route_sigmoid(x: jax.Array, w_router: jax.Array, cfg: MoEConfig,
                  score_bias: jax.Array | None = None,
                  bias: jax.Array | None = None) -> Routing:
    """DeepSeek-V3's group-limited sigmoid routing (``noaux_tc``).

    scores = sigmoid(x W_r) in f32.  Experts are chosen on scores +
    ``score_bias`` (the per-expert correction bias): a group's score is
    the sum of its two best, the ``topk_group`` best of ``n_group``
    groups are kept, and the ``top_k`` best experts inside them are
    chosen.  The bias chooses and does not weigh: the weights are the
    chosen experts' unbiased scores, normalised to sum 1, times
    ``routed_scaling``.  ``bias``: optional (E,) additive logit bias, as
    in ``route``.  ``probs`` of the result holds the scores."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores
    if score_bias is not None:
        choice = scores + score_bias.astype(jnp.float32)
    T, E = scores.shape
    G = cfg.n_group
    groups = choice.reshape(T, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(groups, min(2, E // G))[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, cfg.topk_group)
    keep = jnp.sum(jax.nn.one_hot(kept, G, dtype=jnp.int32), axis=1) > 0
    keep = jnp.repeat(keep, E // G, axis=-1)                       # (T, E)
    _, experts = jax.lax.top_k(jnp.where(keep, choice, -jnp.inf), cfg.top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return Routing(gates * cfg.routed_scaling, experts.astype(jnp.int32),
                   scores)


def route_for(params: dict, x: jax.Array, cfg: MoEConfig) -> Routing:
    """The routing ``cfg`` asks for, from the layer's router weights."""
    if cfg.scoring == "sigmoid":
        return route_sigmoid(x, params["router"], cfg,
                             params.get("score_bias"),
                             params.get("router_bias"))
    return route(x, params["router"], cfg.top_k, params.get("router_bias"))


def require_plain_routing(cfg: MoEConfig, where: str):
    """Refuse, in ``where``, routing that only the monolithic dense path
    serves: sigmoid group-limited scores, or a share of held experts."""
    if cfg.scoring != "softmax":
        raise NotImplementedError(
            f"{where} does not serve {cfg.scoring} group-limited routing "
            f"yet; serve it with the monolithic runtime")
    if not cfg.holds_all:
        raise NotImplementedError(
            f"{where} does not serve held experts ({cfg.held} of "
            f"{cfg.n_experts}) yet; serve them with the monolithic runtime")


def routing_counts(routing: Routing, n_experts: int,
                   weights: jax.Array | None = None) -> jax.Array:
    """Per-expert routed-token counts for one flat batch: (E,) f32.

    The serving runtime accumulates these across decode steps — the
    live traffic trace ``core.load_balance.balance_experts`` re-solves
    placement over (paper §6).  ``weights``: optional (T,) per-token
    weight — the engine passes its active-slot mask so idle KV rows
    (decoded every iteration but serving no request) never pollute the
    trace."""
    one_hot = jax.nn.one_hot(routing.experts, n_experts, dtype=jnp.float32)
    if weights is not None:
        one_hot = one_hot * weights.astype(jnp.float32)[:, None, None]
    return jnp.sum(one_hot, axis=(0, 1))


def _token_hash01(tok_ids: jax.Array) -> jax.Array:
    """Deterministic hash of token index -> [0, 1) f32 (splitmix-style).

    Replica choice must be a pure function of the token's position so a
    rebalanced runtime stays token-identical to the static one."""
    h = tok_ids.astype(jnp.uint32) * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return h.astype(jnp.float32) * jnp.float32(2.0 ** -32)


def replica_assign(experts: jax.Array, rep_node: jax.Array,
                   rep_slot: jax.Array, rep_cum: jax.Array,
                   slots_per_node: int):
    """Map (T, K) expert ids to virtual expert slots under a replicated
    placement (``core.load_balance.PlacementTables``).

    Token t's share of a replicated expert is split deterministically by
    hash of the token index against the replica's cumulative traffic
    fractions.  Returns (vslot (T,K) int32 in [0, N*S), node (T,K)
    int32) — every (token, k) pair lands on exactly one replica, so the
    combined output is identical to the unreplicated dispatch.
    """
    T, _ = experts.shape
    u = _token_hash01(jnp.arange(T, dtype=jnp.int32))          # (T,)
    cum = rep_cum[experts]                                      # (T,K,R)
    r = jnp.sum(u[:, None, None] >= cum, axis=-1).astype(jnp.int32)
    r = jnp.minimum(r, rep_cum.shape[-1] - 1)
    node = jnp.take_along_axis(rep_node[experts], r[..., None], -1)[..., 0]
    slot = jnp.take_along_axis(rep_slot[experts], r[..., None], -1)[..., 0]
    return node * slots_per_node + slot, node


def load_balance_loss(routing: Routing, n_experts: int) -> jax.Array:
    """Switch-transformer auxiliary loss: E * sum_e f_e * p_e."""
    T = routing.probs.shape[0]
    one_hot = jax.nn.one_hot(routing.experts, n_experts, dtype=jnp.float32)
    f = jnp.sum(one_hot, axis=(0, 1)) / T            # fraction routed (sums to K)
    p = jnp.mean(routing.probs, axis=0)
    return n_experts * jnp.sum(f * p) / routing.experts.shape[1]


def expert_capacity(n_tokens: int, cfg: MoEConfig, mode: str) -> int:
    """Static per-expert capacity.  'full' is drop-free (C = T)."""
    if mode == "full":
        return n_tokens
    cf = cfg.capacity_factor if mode == "train" else 2.0 * cfg.capacity_factor
    c = int(-(-n_tokens * cfg.top_k * cf // cfg.n_experts))
    c = max(4, -(-c // 4) * 4)  # multiple of 4, >= 4
    return min(c, n_tokens)


def routed_rows(cfg: MoEConfig, n_tokens: int, mode: str) -> int:
    """Rows the routed-expert matmuls of ``routed_experts_dense`` compute
    for a batch of ``n_tokens``: held experts x capacity."""
    return cfg.held * expert_capacity(n_tokens, cfg, mode)


def dispatch_indices(routing: Routing, n_experts: int, capacity: int,
                     valid: jax.Array | None = None):
    """Compute per-(token,k) slot positions and the (E, C) index buffers.

    valid: optional (T, K) bool — entries marked False are dropped (used by
    the sharded M2N path to keep only locally-owned experts).
    Returns (idx_buf, gate_buf): idx_buf[e, c] = token id feeding expert e
    slot c (sentinel T = empty), gate_buf[e, c] = combine weight.
    """
    T, K = routing.experts.shape
    mask = jax.nn.one_hot(routing.experts, n_experts, dtype=jnp.float32)  # (T,K,E)
    if valid is not None:
        mask = mask * valid[..., None].astype(jnp.float32)
    flat = mask.reshape(T * K, n_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(pos_flat.reshape(T, K, n_experts) * mask, axis=-1).astype(jnp.int32)
    keep = pos < capacity
    if valid is not None:
        keep &= valid
    tok_ids = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, K))
    # invalid entries are routed to an out-of-bounds slot and dropped
    slot = jnp.where(keep, pos, capacity)
    e_flat = routing.experts.reshape(T * K)
    s_flat = slot.reshape(T * K)
    idx_buf = jnp.full((n_experts, capacity), T, dtype=jnp.int32)
    idx_buf = idx_buf.at[e_flat, s_flat].set(tok_ids.reshape(T * K), mode="drop")
    gate_buf = jnp.zeros((n_experts, capacity), dtype=jnp.float32)
    gate_buf = gate_buf.at[e_flat, s_flat].set(
        routing.gates.reshape(T * K), mode="drop")
    return idx_buf, gate_buf


# Pluggable routed-experts implementation.  ``repro.core.m2n`` installs a
# shard_map-based M2N dispatch here; the default is the monolithic
# scatter/gather capacity-buffer path (the paper's "existing system"
# baseline).
_ROUTED_IMPL = None


def set_routed_impl(fn):
    """Install fn(params, x, cfg, act, capacity_mode) -> (y, aux) or None."""
    global _ROUTED_IMPL
    prev = _ROUTED_IMPL
    _ROUTED_IMPL = fn
    return prev


def routed_experts_dense(params: dict, x: jax.Array, cfg: MoEConfig, act: str,
                         capacity_mode: str):
    """Baseline routed-expert computation (monolithic scatter/gather)."""
    T, d = x.shape
    with jax.named_scope("router"):
        routing = route_for(params, x, cfg)
        aux = (load_balance_loss(routing, cfg.n_experts)
               if cfg.scoring == "softmax" else jnp.zeros((), jnp.float32))
        C = expert_capacity(T, cfg, capacity_mode)
        if cfg.holds_all:
            idx_buf, gate_buf = dispatch_indices(routing, cfg.n_experts, C)
        else:
            # only the (token, k) pairs routed to a held expert
            local = routing.experts - cfg.first_held
            held = (local >= 0) & (local < cfg.held)
            idx_buf, gate_buf = dispatch_indices(
                Routing(routing.gates, jnp.where(held, local, 0),
                        routing.probs), cfg.held, C, valid=held)

    with jax.named_scope("experts"):
        # gather tokens into (E, C, d) expert buffers
        xe = x.at[idx_buf].get(mode="fill", fill_value=0)
        # per-expert gated MLP: (E,C,d) x (E,d,f) -> (E,C,f) -> (E,C,d)
        h = activation(jnp.einsum("ecd,edf->ecf", xe, params["we1"]), act)
        h = h * jnp.einsum("ecd,edf->ecf", xe, params["we3"])
        out = jnp.einsum("ecf,efd->ecd", h, params["we2"])

    with jax.named_scope("combine"):
        # weighted scatter-add combine
        y = jnp.zeros((T, d), dtype=jnp.float32)
        w = out.astype(jnp.float32) * gate_buf[..., None]
        y = y.at[idx_buf.reshape(-1)].add(w.reshape(-1, d), mode="drop")
    return y.astype(x.dtype), aux


def moe_ffn(params: dict, x: jax.Array, cfg: MoEConfig, act: str,
            capacity_mode: str = "train"):
    """MoE FFN over a flat token batch.

    params: {"router": (d,E), "we1"/"we3": (E_held,d,ffe),
             "we2": (E_held,ffe,d), optional "score_bias": (E,) (sigmoid
             routing), optional shared expert ws1/ws3/ws2 (+ optional
             "shared_gate": (d,)), optional dense residual wd1/wd3/wd2}
    x: (T, d).  Returns (y: (T, d), aux_loss: scalar f32).
    """
    impl = _ROUTED_IMPL if _ROUTED_IMPL is not None else routed_experts_dense
    y, aux = impl(params, x, cfg, act, capacity_mode)

    if "ws1" in params:  # shared experts (always active)
        shared = gated_ffn(x, params["ws1"], params["ws3"], params["ws2"], act)
        if "shared_gate" in params:     # qwen2-moe: a sigmoid gate
            g = jax.nn.sigmoid(x.astype(jnp.float32) @ params["shared_gate"].astype(jnp.float32))
            y = y + (g[:, None] * shared.astype(jnp.float32)).astype(x.dtype)
        else:                           # deepseek: added as it is
            y = y + shared
    if "wd1" in params:  # arctic parallel dense residual
        y = y + gated_ffn(x, params["wd1"], params["wd3"], params["wd2"], act)
    return y, aux
