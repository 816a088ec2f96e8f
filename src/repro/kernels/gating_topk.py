"""Fused gating Pallas TPU kernels (paper §6 "fused kernels").

Attention nodes must, per token: run the router GEMM, softmax, select
top-k experts, normalize combine weights, and produce per-expert token
counts for the M2N dispatch.  Done naively this is a chain of small
memory-bound ops; the paper fuses them into one kernel.  Here the whole
chain runs on one VMEM-resident (Tb, E) logits tile per grid step.

Two kernels share the router-GEMM → softmax → iterative-top-k core:

``gating_topk`` — gates (T,K) f32, experts (T,K) int32, per-block expert
counts (nb, E) int32 (summed by the ops wrapper to global counts — the
"tokens per expert node" header the M2N sender needs).

``gating_dispatch`` — the full fused dispatch build the serving hot path
uses: router GEMM + bias, softmax, top-k, optional replica assignment
against live placement tables (``models.moe.replica_assign`` semantics,
token-index hash recomputed in-kernel), shard-ownership filter, and
capacity-slot positions.  Slot order is exactly
``models.moe.dispatch_indices``'s token-major first-come-first-served
order: within a block via a strictly-lower-triangular 0/1 matmul over
per-token bucket one-hots (Mosaic has no cumsum), across blocks via a
VMEM scratch of running per-bucket occupancy (the grid is sequential,
so block i+1 sees the totals of blocks 0..i).  The (n_buckets, C)
index/gate buffer scatter stays in the jnp wrapper — TPU kernels avoid
in-kernel scatters; the fusion win is eliminating the memory-bound
(T*K, E) one-hot cumsum chain and the separate router/top-k passes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode


def _topk_core(x, w, top_k: int, bias=None):
    """Router GEMM (+ optional logit bias) → softmax → iterative top-k.

    Returns (gates (Tb,K) f32 normalized, experts (Tb,K) int32) —
    identical selection/tie-breaking to ``jax.lax.top_k`` (argmax picks
    the lowest index on ties, like top_k's stable sort)."""
    logits = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    if bias is not None:
        logits = logits + bias
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    # iterative top-k: k rounds of (argmax, mask) — k is small and static
    remaining = probs
    gate_cols, idx_cols = [], []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        g = jnp.max(remaining, axis=-1)
        gate_cols.append(g)
        idx_cols.append(idx.astype(jnp.int32))
        remaining = remaining * (1.0 - jax.nn.one_hot(idx, E, dtype=jnp.float32))
    gates = jnp.stack(gate_cols, axis=-1)
    idx = jnp.stack(idx_cols, axis=-1)
    return gates / jnp.sum(gates, axis=-1, keepdims=True), idx


def _kernel(x_ref, w_ref, gates_ref, idx_ref, counts_ref, *, top_k: int):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    gates, idx = _topk_core(x, w, top_k)
    E = w.shape[-1]
    gates_ref[...] = gates
    idx_ref[...] = idx
    counts = sum(_one_hot(idx[:, k:k + 1], E) for k in range(top_k))
    counts_ref[...] = jnp.sum(counts, axis=0, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("top_k", "tb", "interpret"))
def gating_topk(x: jax.Array, w_router: jax.Array, top_k: int, *,
                tb: int = 256, interpret: Optional[bool] = None):
    """x: (T, d), w_router: (d, E) -> (gates (T,K), experts (T,K), counts (E,)).

    VMEM per step: Tb*d (x) + d*E (router) + Tb*E (logits) — for
    arctic-480b (d=7168, E=128, Tb=256) ~5.7 MB bf16/f32.
    """
    T, d = x.shape
    E = w_router.shape[1]
    tb = _token_block(T, tb)
    grid = (T // tb,)
    gates, idx, counts = pl.pallas_call(
        functools.partial(_kernel, top_k=top_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, d), lambda i: (i, 0)),
            pl.BlockSpec((d, E), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, top_k), lambda i: (i, 0)),
            pl.BlockSpec((tb, top_k), lambda i: (i, 0)),
            pl.BlockSpec((None, 1, E), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, top_k), jnp.float32),
            jax.ShapeDtypeStruct((T, top_k), jnp.int32),
            jax.ShapeDtypeStruct((grid[0], 1, E), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
        name="gating_topk",
    )(x, w_router)
    return gates, idx, jnp.sum(counts, axis=(0, 1))


def _hash01(tok):
    """In-kernel twin of ``models.moe._token_hash01`` (splitmix-style):
    token index (int32) -> [0, 1) f32, bit-identical to the jnp path.
    Written in int32 arithmetic with logical shifts (the same bit
    patterns as its uint32 form), and the final uint32 -> f32 conversion
    done as hi * 2^16 + lo: both halves convert exactly, so the one
    rounding of the f32 add equals the rounding of the direct
    conversion."""
    srl = jax.lax.shift_right_logical
    h = tok * jnp.int32(-1640531535)                  # 2654435761 as int32
    h = h ^ srl(h, jnp.int32(16))
    h = h * jnp.int32(-2048144777)                    # 2246822519 as int32
    h = h ^ srl(h, jnp.int32(13))
    hi = srl(h, jnp.int32(16)).astype(jnp.float32)
    lo = (h & jnp.int32(0xFFFF)).astype(jnp.float32)
    return (hi * jnp.float32(65536.0) + lo) * jnp.float32(2.0 ** -32)


def _one_hot(col, n: int):
    """(Tb, 1) int32 -> (Tb, n) f32 one-hot, by iota compare."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (col.shape[0], n), 1)
    return (col == iota).astype(jnp.float32)


def _dot(a, b, precision=None):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _dispatch_kernel(*refs, top_k: int, n_buckets: int, slots_per_node: int,
                     tb: int, use_tables: bool):
    if use_tables:
        (own_ref, x_ref, w_ref, b_ref, cw_ref, rn_ref, rs_ref, rc_ref,
         gates_ref, bucket_ref, pos_ref, valid_ref, counts_ref,
         base_ref) = refs
    else:
        (own_ref, x_ref, w_ref, b_ref, cw_ref,
         gates_ref, bucket_ref, pos_ref, valid_ref, counts_ref,
         base_ref) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero_base():
        # running per-bucket occupancy carried across sequential grid
        # steps — the cross-block half of dispatch_indices' cumsum
        base_ref[...] = jnp.zeros_like(base_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    gates, experts = _topk_core(x, w, top_k, bias=b_ref[...])
    gates_ref[...] = gates
    E = w.shape[-1]
    own = own_ref[0]
    cw = cw_ref[...]                                          # (Tb, 1)
    if use_tables:
        # replica_assign: hash the *global* token index against the
        # replica cumulative-traffic fractions; all (E,R) table lookups
        # are exact one_hot matmuls (no dynamic gather on TPU)
        R = rc_ref.shape[-1]
        tok = i * tb + jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
        u = _hash01(tok)                                      # (Tb, 1)
        hi = jax.lax.Precision.HIGHEST
        tables = [t_ref[...].astype(jnp.float32)
                  for t_ref in (rn_ref, rs_ref, rc_ref)]

    counts = jnp.zeros((1, E), jnp.float32)
    ohs = []
    for k in range(top_k):
        e_k = experts[:, k:k + 1]                             # (Tb, 1)
        oh_e = _one_hot(e_k, E)                               # (Tb, E)
        # per-original-expert weighted counts (the live traffic trace)
        # — computed before any replica split, like the jnp path
        counts = counts + jnp.sum(oh_e * cw, axis=0, keepdims=True)
        if use_tables:
            rn, rs, rc = (_dot(oh_e, t, hi) for t in tables)  # (Tb, R)
            r = jnp.sum((u >= rc).astype(jnp.int32), axis=-1, keepdims=True)
            oh_r = _one_hot(jnp.minimum(r, R - 1), R)
            node = jnp.sum(rn * oh_r, -1, keepdims=True).astype(jnp.int32)
            slot = jnp.sum(rs * oh_r, -1, keepdims=True).astype(jnp.int32)
            vslot = node * slots_per_node + slot
            mine = node == own
        else:
            vslot = e_k
            # node == own  <=>  e in [own * spn, (own + 1) * spn)
            mine = ((vslot >= own * slots_per_node)
                    & (vslot < (own + 1) * slots_per_node))
        valid = (own < 0) | mine                              # (Tb, 1)
        bucket_ref[:, k:k + 1] = vslot
        valid_ref[:, k:k + 1] = valid.astype(jnp.int32)
        # only valid entries occupy a capacity slot
        ohs.append(_one_hot(vslot, n_buckets) * valid.astype(jnp.float32))
    counts_ref[...] = counts

    # capacity-slot positions in dispatch_indices' token-major order: the
    # entries ahead of (t, k) are every (t' < t, any k') plus (t, k' < k).
    # The first part is one strictly-lower-triangular 0/1 matmul over the
    # per-token bucket totals (exact: 0/1 and small-integer operands,
    # f32 accumulation, counts far below 2^24).
    per_tok = sum(ohs)                                        # (Tb, B)
    row = jax.lax.broadcasted_iota(jnp.int32, (tb, tb), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, tb), 1)
    lower = (col < row).astype(jnp.float32)
    ahead = _dot(lower, per_tok) + base_ref[...]              # (Tb, B)
    for k in range(top_k):
        pos = jnp.sum(ahead * ohs[k], axis=-1, keepdims=True)
        pos_ref[:, k:k + 1] = pos.astype(jnp.int32)
        ahead = ahead + ohs[k]
    base_ref[...] = base_ref[...] + jnp.sum(per_tok, axis=0, keepdims=True)


def _token_block(T: int, tb: int) -> int:
    """Token block: all of T when it fits in ``tb``, else ``tb`` halved
    until it divides T (no lower than 8, the sublane count a partial
    block needs); all of T when no such block divides it."""
    if T <= tb:
        return T
    while T % tb and tb > 8:
        tb //= 2
    return tb if T % tb == 0 else T


@functools.partial(jax.jit, static_argnames=("top_k", "n_buckets",
                                             "capacity", "slots_per_node",
                                             "tb", "interpret"))
def gating_dispatch(x: jax.Array, w_router: jax.Array, top_k: int,
                    n_buckets: int, capacity: int, *,
                    bias=None, count_weights=None, owner=None,
                    rep_node=None, rep_slot=None, rep_cum=None,
                    slots_per_node: int = 0, tb: int = 256,
                    interpret: Optional[bool] = None):
    """Fused router → top-k → dispatch-index build.

    x: (T, d), w_router: (d, E).  Returns
    (idx_buf (rows, capacity) int32 with sentinel T = empty,
     gate_buf (rows, capacity) f32,
     counts (E,) f32 weighted per-original-expert routed-token counts),
    bit-matching the ``route`` + ``replica_assign`` + ``dispatch_indices``
    jnp chain (``kernels.ref.gating_dispatch_ref``).

    ``n_buckets``: dispatch bucket count — E for plain expert dispatch,
    N*S virtual slots under live placement tables.  Tokens past
    ``capacity`` per bucket are dropped first-come-first-served (the
    ``capacity_mode != 'full'`` drop semantics).

    ``owner``: optional traced shard id (``jax.lax.axis_index`` inside
    the m2n shard_map) — only (token, k) pairs whose bucket's node
    (``bucket // slots_per_node``) equals ``owner`` occupy a slot, and
    the returned buffers cover that node's ``slots_per_node`` local
    buckets (rows = slots_per_node).  None keeps every pair and returns
    global (rows = n_buckets) buffers.  It reaches the kernel in SMEM as
    a scalar-prefetch argument.

    ``rep_node``/``rep_slot``/``rep_cum``: optional (E, R) live placement
    tables (``core.load_balance.PlacementTables``); the kernel then maps
    each (token, k) to one replica's virtual slot via the deterministic
    token-index hash, exactly like ``models.moe.replica_assign``.
    """
    T, d = x.shape
    E = w_router.shape[1]
    use_tables = rep_node is not None
    if not slots_per_node:
        slots_per_node = n_buckets
    tb = _token_block(T, tb)
    grid = (T // tb,)
    b = (jnp.zeros((E,), jnp.float32) if bias is None else bias)
    cw = (jnp.ones((T,), jnp.float32) if count_weights is None
          else count_weights.astype(jnp.float32))
    own = (jnp.full((1,), -1, jnp.int32) if owner is None
           else jnp.asarray(owner, jnp.int32).reshape(1))
    inputs = [own, x, w_router, b.astype(jnp.float32).reshape(1, E),
              cw.reshape(T, 1)]
    in_specs = [
        pl.BlockSpec((tb, d), lambda i, o: (i, 0)),
        pl.BlockSpec((d, E), lambda i, o: (0, 0)),
        pl.BlockSpec((1, E), lambda i, o: (0, 0)),
        pl.BlockSpec((tb, 1), lambda i, o: (i, 0)),
    ]
    if use_tables:
        R = rep_cum.shape[-1]
        inputs += [rep_node.astype(jnp.int32), rep_slot.astype(jnp.int32),
                   rep_cum.astype(jnp.float32)]
        in_specs += [pl.BlockSpec((E, R), lambda i, o: (0, 0))] * 3
    tok_spec = pl.BlockSpec((tb, top_k), lambda i, o: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[tok_spec] * 4 + [
            pl.BlockSpec((None, 1, E), lambda i, o: (i, 0, 0))],
        scratch_shapes=[pltpu.VMEM((1, n_buckets), jnp.float32)],
    )
    gates, bucket, pos, valid, counts = pl.pallas_call(
        functools.partial(_dispatch_kernel, top_k=top_k,
                          n_buckets=n_buckets,
                          slots_per_node=slots_per_node, tb=tb,
                          use_tables=use_tables),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, top_k), jnp.float32),
            jax.ShapeDtypeStruct((T, top_k), jnp.int32),
            jax.ShapeDtypeStruct((T, top_k), jnp.int32),
            jax.ShapeDtypeStruct((T, top_k), jnp.int32),
            jax.ShapeDtypeStruct((grid[0], 1, E), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
        name="gating_dispatch",
    )(*inputs)

    # (rows, C) buffer scatter — stays jnp (no in-kernel scatter on TPU)
    if owner is None:
        rows, b_idx = n_buckets, bucket
    else:
        rows = slots_per_node
        b_idx = bucket - jnp.asarray(owner, jnp.int32) * slots_per_node
    keep = (valid > 0) & (pos < capacity)
    # dropped/foreign entries land in the out-of-bounds capacity column
    # (row clamped in-range so mode="drop" keys off the column alone)
    slot = jnp.where(keep, pos, capacity)
    b_idx = jnp.clip(b_idx, 0, rows - 1)
    tok = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                           (T, top_k))
    bf, sf = b_idx.reshape(-1), slot.reshape(-1)
    idx_buf = jnp.full((rows, capacity), T, jnp.int32)
    idx_buf = idx_buf.at[bf, sf].set(tok.reshape(-1), mode="drop")
    gate_buf = jnp.zeros((rows, capacity), jnp.float32)
    gate_buf = gate_buf.at[bf, sf].set(gates.reshape(-1), mode="drop")
    return idx_buf, gate_buf, jnp.sum(counts, axis=(0, 1))
