"""M2N token dispatch — the paper's §5 communication library, adapted to TPU.

The paper replaces NCCL's grouped peer-to-peer all-to-all with direct
RDMA writes sized to the actual routed traffic.  On a TPU mesh the
analogous waste in the monolithic baseline is *structural*: the
scatter/gather dispatch under automatic SPMD partitioning makes XLA
all-gather full token activations and expert buffers across the expert
axis (every shard receives every token, routed or not).

This module provides the TPU-native equivalent of M2N: a ``shard_map``
region in which each expert shard

  1. computes routing for the tokens it already holds (replicated across
     the expert axis — the "gating on attention nodes" of the paper),
  2. gathers ONLY the tokens routed to its locally-owned experts into
     per-expert capacity buffers (zero cross-shard traffic for dispatch),
  3. runs its complete per-expert GEMMs (EP property the paper relies on),
  4. contributes its weighted partial outputs to a single
     ``psum_scatter``-able reduction over the expert axis (the combine —
     the only wire traffic, sized T_local x d exactly).

Install it around any jitted forward with ``use_m2n(mesh, ...)``; every
MoE layer in the model then routes through this path.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.config import MoEConfig
from repro.models import moe as moe_lib
from repro.models.common import activation


def _pad_experts(w: jax.Array, e_pad: int) -> jax.Array:
    e = w.shape[0]
    if e_pad == e:
        return w
    return jnp.pad(w, ((0, e_pad - e),) + ((0, 0),) * (w.ndim - 1))


def sharded_routed_experts(params: dict, x: jax.Array, cfg: MoEConfig,
                           act: str, capacity_mode: str, *,
                           mesh: jax.sharding.Mesh,
                           data_axes: Sequence[str] = ("data",),
                           expert_axis: str = "model",
                           weights_2d: bool = False,
                           tables: Optional[dict] = None,
                           with_counts: bool = False,
                           count_weights: Optional[jax.Array] = None,
                           transport=None,
                           use_kernels: bool = False):
    """M2N routed-experts computation under shard_map.

    x: (T, d) sharded over ``data_axes``; expert weights sharded over
    ``expert_axis``.  Returns (y (T,d), aux scalar) — plus a per-expert
    routed-token count vector (E,) when ``with_counts`` (the live
    traffic trace the serving engine feeds the §6 load balancer;
    ``count_weights`` (T,) optionally masks rows out of the trace, e.g.
    idle KV slots).

    weights_2d: additionally shard the expert d_ff dimension over the
    data axes (weight-stationary 2D — the §Perf pair-1 iteration-2
    optimization).  Decode activations are tiny, so each shard
    all-gathers the tokens over the data axes, computes its (expert
    slice x d_ff slice) of the MLP, and the f-partial products are
    psum'd over the data axes.  Intended for decode-sized batches.

    transport: optional ``core.transport.Transport`` — the combine psum
    (this dispatch's only wire traffic) is accounted on it as a
    "collective" hop with its analytic byte count.  Accounting happens
    when this function executes Python-side; under an enclosing ``jit``
    that is trace time, so jitted serving paths account the hop at the
    runtime level instead (``core.disagg`` does).

    use_kernels: run the shard-local hot path on the Pallas kernels —
    the fused ``gating_dispatch`` (router matmul → top-k → owner-filtered
    dispatch buffers, placement tables included) replaces the ``route``
    + ``replica_assign`` + ``dispatch_indices`` chain, and the three
    per-expert einsums become ``kops.grouped_mlp`` with the
    capacity-drop-aware row mask.  The kernel path reports ``aux = 0``
    (the serving decode paths never consume the load-balance loss) and
    is token-parity with the jnp path; not supported with
    ``weights_2d``.

    tables: executable expert placement (jax arrays mirroring
    ``core.load_balance.PlacementTables``: rep_node/rep_slot/rep_cum
    (E, R) plus int "slots_per_node").  When set, ``params["we*"]`` must
    be the *virtual-slot* weights gathered node-major to (N*S, d, f),
    expert ownership follows the placement's (possibly replicated)
    replica assignment — split deterministically by token-index hash —
    instead of the contiguous-block default, and the output stays
    token-identical to the unreplicated dispatch.
    """
    moe_lib.require_plain_routing(cfg, "the M2N dispatch")
    n_shards = mesh.shape[expert_axis]
    E = cfg.n_experts
    if use_kernels and weights_2d:
        raise NotImplementedError("use_kernels is not supported with "
                                  "weights_2d")
    if tables is not None:
        if weights_2d:
            raise NotImplementedError("placement tables are not supported "
                                      "with weights_2d")
        S = int(tables["slots_per_node"])
        e_loc = S
        we1, we3, we2 = params["we1"], params["we3"], params["we2"]
        if we1.shape[0] != n_shards * S:
            raise ValueError(f"placed expert weights must be gathered to "
                             f"(N*S={n_shards * S}, ...), got {we1.shape}")
        tbl_args = (tables["rep_node"], tables["rep_slot"],
                    tables["rep_cum"])
    else:
        e_pad = -(-E // n_shards) * n_shards
        e_loc = e_pad // n_shards
        we1 = _pad_experts(params["we1"], e_pad)
        we3 = _pad_experts(params["we3"], e_pad)
        we2 = _pad_experts(params["we2"], e_pad)
        tbl_args = ()
    router_w = params["router"]
    bias = params.get("router_bias")
    if bias is None:
        bias = jnp.zeros((E,), jnp.float32)
    if count_weights is None:
        count_weights = jnp.ones((x.shape[0],), jnp.float32)
    dtuple = tuple(data_axes)

    def local_fn(x_loc, router_w, bias, cw, w1, w3, w2, *tbl):
        if weights_2d and dtuple:
            # gather the (tiny) token batch so every shard sees all rows
            x_all = jax.lax.all_gather(x_loc, dtuple, axis=0, tiled=True)
            cw = jax.lax.all_gather(cw, dtuple, axis=0, tiled=True)
        else:
            x_all = x_loc
        t_all = x_all.shape[0]
        cap = moe_lib.expert_capacity(t_all, cfg, capacity_mode)
        j = jax.lax.axis_index(expert_axis)
        if use_kernels:
            # fused Pallas path: router matmul -> top-k -> owner-filtered
            # dispatch buffers in one kernel; the decode serving paths
            # never consume the aux loss, so it is pinned to 0 here.
            from repro.kernels import ops as kops
            tk = dict(zip(("rep_node", "rep_slot", "rep_cum"), tbl))
            with jax.named_scope("m2n_dispatch"):
                idx_buf, gate_buf, counts = kops.gating_dispatch(
                    x_all, router_w, cfg.top_k, n_buckets=n_shards * e_loc,
                    capacity=cap, bias=bias, count_weights=cw, owner=j,
                    slots_per_node=e_loc, **tk)
                aux = jnp.zeros((), jnp.float32)
                xe = x_all.at[idx_buf].get(mode="fill", fill_value=0)
            # 3'. grouped per-expert MLP kernel, dropped/empty capacity
            #     slots masked to exact zeros
            with jax.named_scope("experts"):
                out = kops.grouped_mlp(xe, w1, w3, w2, act,
                                       row_valid=idx_buf < t_all)
        else:
            with jax.named_scope("m2n_dispatch"):
                # 1. routing — replicated across the expert axis (paper:
                #    gating is fused on the attention side; every expert
                #    shard knows the plan)
                routing = moe_lib.route(x_all, router_w, cfg.top_k, bias)
                aux = moe_lib.load_balance_loss(routing, E)
                counts = moe_lib.routing_counts(routing, E, cw)
                if tbl:
                    # placement-table ownership: token-hash replica
                    # assignment
                    vslot, node = moe_lib.replica_assign(
                        routing.experts, *tbl, slots_per_node=e_loc)
                    local = node == j
                    local_ids = jnp.where(local, vslot - j * e_loc, 0)
                else:
                    owner = routing.experts // e_loc
                    local = owner == j
                    local_ids = jnp.where(local, routing.experts - j * e_loc,
                                          0)
                # 2. dispatch: gather ONLY locally-routed tokens — no wire
                #    traffic
                r_loc = moe_lib.Routing(routing.gates, local_ids,
                                        routing.probs)
                idx_buf, gate_buf = moe_lib.dispatch_indices(
                    r_loc, e_loc, cap, valid=local)
                xe = x_all.at[idx_buf].get(mode="fill", fill_value=0)
            # 3. complete per-expert GEMMs on the local shard (d_ff
            #    possibly sliced over the data axes in weights_2d mode)
            with jax.named_scope("experts"):
                h = activation(jnp.einsum("ecd,edf->ecf", xe, w1), act)
                h = h * jnp.einsum("ecd,edf->ecf", xe, w3)
                out = jnp.einsum("ecf,efd->ecd", h, w2)
                if weights_2d and dtuple:
                    out = jax.lax.psum(out, dtuple)    # reduce f-partials
        # 4. combine: weighted partial sum, reduced over the expert axis.
        with jax.named_scope("m2n_combine"):
            y = jnp.zeros((t_all, x_all.shape[1]), jnp.float32)
            w = out.astype(jnp.float32) * gate_buf[..., None]
            y = y.at[idx_buf.reshape(-1)].add(
                w.reshape(-1, x_all.shape[1]), mode="drop")
            y = jax.lax.psum(y, expert_axis)
        if weights_2d and dtuple:
            # back to this shard's rows
            idx = jnp.zeros((), jnp.int32)
            for a in dtuple:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            t_loc = x_loc.shape[0]
            y = jax.lax.dynamic_slice_in_dim(y, idx * t_loc, t_loc, 0)
        aux = jax.lax.pmean(aux, dtuple) if dtuple else aux
        if dtuple and not weights_2d:
            # routing ran per data shard over local rows only
            counts = jax.lax.psum(counts, dtuple)
        res = (y.astype(x_loc.dtype), aux)
        return res + (counts,) if with_counts else res

    w_specs = (P(expert_axis, None, dtuple), P(expert_axis, None, dtuple),
               P(expert_axis, dtuple, None)) if weights_2d else (
        P(expert_axis, None, None), P(expert_axis, None, None),
        P(expert_axis, None, None))
    tbl_specs = (P(None, None),) * len(tbl_args)
    out_specs = (P(dtuple, None), P())
    if with_counts:
        out_specs = out_specs + (P(),)
    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dtuple, None), P(None, None), P(None), P(dtuple))
        + w_specs + tbl_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    if transport is not None and n_shards > 1:
        itemsize = jnp.dtype(x.dtype).itemsize
        transport.record_collective(
            m2n_traffic_bytes(x.shape[0], x.shape[1], cfg.top_k, E,
                              n_shards, itemsize)["m2n"],
            fanout=n_shards)
    return fn(x, router_w, bias, count_weights, we1, we3, we2, *tbl_args)


@contextlib.contextmanager
def use_m2n(mesh: jax.sharding.Mesh, data_axes: Sequence[str] = ("data",),
            expert_axis: str = "model", weights_2d: bool = False,
            transport=None, use_kernels: bool = False):
    """Context manager: route every MoE layer through the M2N dispatch.

    ``transport`` threads a ``core.transport.Transport`` into every
    dispatch for combine-traffic accounting (see
    ``sharded_routed_experts`` for the jit caveat); ``use_kernels``
    selects the fused Pallas dispatch + grouped-MLP shard path."""

    def impl(params, x, cfg, act, capacity_mode):
        return sharded_routed_experts(
            params, x, cfg, act, capacity_mode, mesh=mesh,
            data_axes=data_axes, expert_axis=expert_axis,
            weights_2d=weights_2d, transport=transport,
            use_kernels=use_kernels)

    prev = moe_lib.set_routed_impl(impl)
    try:
        yield
    finally:
        moe_lib.set_routed_impl(prev)


def m2n_traffic_bytes(t_local: int, d_model: int, top_k: int,
                      n_experts: int, n_expert_shards: int,
                      bytes_per_el: int = 2) -> dict:
    """Analytic wire traffic per MoE layer for the three dispatch schemes.

    Used by the roofline analysis and the fig10/11 benchmarks to compare
    the baseline (all-gather everything), classic EP all-to-all, and the
    M2N combine-only scheme above.
    """
    allgather = t_local * d_model * (n_expert_shards - 1) * bytes_per_el * 2
    a2a = 2 * t_local * top_k * d_model * bytes_per_el * (
        (n_expert_shards - 1) / n_expert_shards)
    m2n = t_local * d_model * bytes_per_el * (
        (n_expert_shards - 1) / n_expert_shards) * 2  # reduce-scatter+all-gather
    return {"baseline_allgather": allgather, "ep_all2all": a2a, "m2n": m2n}
