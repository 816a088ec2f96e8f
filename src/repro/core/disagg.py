"""Disaggregated expert parallelism runtime (paper §3-§4).

The paper's architecture proper: attention modules and expert modules on
*disjoint* device groups.

  * attention group — data-parallel mesh ("dp",): attention weights
    replicated, per-request KV caches sharded over dp.  The router
    (gating) runs here, fused with dispatch preparation (paper §6).
  * expert group — expert-parallel mesh ("ep",): routed expert weights
    sharded by expert id (each "expert node" holds complete experts —
    complete GEMMs, the EP property of §2.2).  Dense archs degenerate to
    E=1 with the FFN weight TP-sharded over "ep" on the hidden dim.

Per decode step and layer, each micro-batch does
  attn phase (dp mesh) -> M2N dispatch -> expert phase (ep mesh)
  -> N2M return -> combine (dp mesh),
where the M2N/N2M hops are cross-mesh ``jax.device_put`` resharding —
the JAX analogue of the paper's RDMA write path (receiver-addressed,
sized to the routed traffic, no host staging).  Ping-pong overlap falls
out of JAX async dispatch: the python loop issues attn(mb+1) before
blocking on expert(mb); with disjoint device groups both run
concurrently.  Shared experts and arctic's dense residual are computed
on the attention side (they are batch-dense — paper's placement).

This runtime is the *decode cluster* only — it does not own prefill.
Prompt processing lives on its own device group
(``serving.prefill.PrefillWorker``) and completed requests' KV rows
arrive via ``serving.kvcache.migrate_kv`` onto ``kv_sharding`` (the
attention group owns the KV cache).  Pass ``devices=`` the decode
cluster's device pool when some local devices are reserved for prefill
(``launch.mesh.split_serving_devices``).

Applicability (DESIGN.md §Arch-applicability): layer kinds attn/local
with dense or MoE FFN.  SSM/RG-LRU/cross layers have no separable FFN
stage here and are served by the monolithic engine instead.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.config import MLA_KINDS, ModelConfig
from repro.core import load_balance as lb_lib
from repro.core import m2n as m2n_lib
from repro.core import pingpong
from repro.core import transport as transport_lib
from repro.models import moe as moe_lib
from repro.models.common import rms_norm
from repro.models.ffn import gated_ffn
from repro.models.transformer import (_lm_head, _embed_tokens, init_cache,
                                      self_attn_decode_sublayer)

EXPERT_KEYS = ("we1", "we3", "we2")

# pipeline stages timed by the runtime (attention compute, M2N dispatch
# hop, expert compute, N2M return hop, attention-side combine)
STAGES = ("attn", "m2n", "expert", "n2m", "combine")


def _layer_index(cfg: ModelConfig, l: int):
    """layer l -> (pattern position or remainder index, block index)."""
    np_, nr = len(cfg.block_pattern), len(cfg.remainder_pattern)
    scanned = cfg.n_blocks * np_
    if l < scanned:
        return ("block", l % np_, l // np_)
    return ("remainder", l - scanned, None)


@jax.jit
def _take_layer(tree, i):
    """Layer ``i`` of a stacked parameter tree, in one program (eager
    indexing materializes each leaf's slice before dropping its leading
    axis, a transient of a whole leaf)."""
    return jax.tree.map(lambda a: a[i], tree)


def _slice_layer_params(params: dict, cfg: ModelConfig, l: int) -> dict:
    where, pos, blk = _layer_index(cfg, l)
    if where == "block":
        return _take_layer(params["blocks"][pos], blk)
    return params["remainder"][pos]


def per_device(mesh: Mesh, spec, fn):
    """``fn`` run per device of ``mesh`` under ``shard_map``, every
    argument and result laid out by ``spec``.  XLA cannot partition a
    Mosaic kernel across devices, so a stage that calls one on a
    multi-device mesh is handed to each device whole; on one device
    this is ``fn`` itself."""
    if mesh.size == 1:
        return fn
    return shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                     check_vma=False)


def _layer_kind(cfg: ModelConfig, l: int) -> str:
    where, pos, _ = _layer_index(cfg, l)
    return (cfg.block_pattern[pos] if where == "block"
            else cfg.remainder_pattern[pos])


@dataclass
class DisaggPlan:
    n_microbatches: int = 3
    capacity_mode: str = "full"
    # route the hot path through the Pallas kernels (compiled on TPU,
    # interpret mode elsewhere) — §6 "fused kernels"
    use_kernels: bool = False
    # route MoE layers through the shard_map M2N dispatch (repro.core.m2n):
    # routing is computed per expert shard, only locally-owned tokens are
    # gathered, and the combine is the single psum over the expert axis
    use_m2n: bool = False
    # block after every stage so stage_report() reflects device wall time
    # (accurate but serialising; leave False to keep the pipeline async)
    profile_stages: bool = False
    # per-node virtual expert slot budget for live placements, as a
    # multiple of ceil(E/N) — headroom for hot-expert replicas (§6).
    # Fixed at construction so rebalances never change jitted shapes.
    replication_slots: float = 2.0


class DisaggregatedInstance:
    """One model replica served with disaggregated expert parallelism."""

    def __init__(self, cfg: ModelConfig, params: dict,
                 attn_devices: Optional[Sequence] = None,
                 expert_devices: Optional[Sequence] = None,
                 plan: Optional[DisaggPlan] = None,
                 devices: Optional[Sequence] = None,
                 transport=None):
        """``devices``: the decode cluster's device pool (default: all
        local devices), split half attention / half expert unless
        ``attn_devices``/``expert_devices`` pin the groups explicitly.
        Serving launchers pass the pool left over after reserving the
        prefill cluster.

        ``transport``: the ``core.transport.Transport`` every token/KV/
        weight hop goes through (M2N dispatch, N2M return, live-placement
        weight regathers) — default a private ``InProcessTransport``.
        The serving engine reuses this instance so one stats ledger
        covers the whole serving path."""
        # plans are mutated in place (auto-m, profile toggling), so each
        # instance must own its own default rather than share one
        plan = plan if plan is not None else DisaggPlan()
        self.transport = (transport if transport is not None
                          else transport_lib.InProcessTransport())
        for kind in cfg.layer_kinds:
            if kind in MLA_KINDS:
                raise NotImplementedError(
                    f"the disaggregated runtime does not serve multi-head "
                    f"latent attention (layer kind {kind!r}, {cfg.name}) "
                    f"yet; use the monolithic engine")
            if kind not in ("attn", "local"):
                raise NotImplementedError(
                    f"disaggregated runtime does not support layer kind "
                    f"{kind!r} ({cfg.name}); use the monolithic engine "
                    f"(see DESIGN.md §Arch-applicability)")
        if cfg.moe is not None:
            moe_lib.require_plain_routing(cfg.moe, "the disaggregated runtime")
        devs = list(devices) if devices is not None else jax.devices()
        attn_devices = list(attn_devices or devs[: max(1, len(devs) // 2)])
        expert_devices = list(expert_devices or devs[max(1, len(devs) // 2):]
                              or devs[:1])
        self.cfg = cfg
        self.plan = plan
        self.attn_mesh = Mesh(np.array(attn_devices), ("dp",))
        self.expert_mesh = Mesh(np.array(expert_devices), ("ep",))
        self.n_expert_nodes = len(expert_devices)

        # ---- split parameters: attention side vs expert side -------------
        def attn_side(tree):
            return {k: v for k, v in tree.items() if k not in EXPERT_KEYS}

        self.layers_attn: List[dict] = []
        self.layers_expert: List[dict] = []
        routers: List[dict] = []
        for l in range(cfg.n_layers):
            lp = _slice_layer_params(params, cfg, l)
            self.layers_attn.append(attn_side(lp))
            if cfg.moe is not None:
                self.layers_expert.append({k: lp[k] for k in EXPERT_KEYS})
                routers.append({k: lp[k] for k in ("router", "router_bias")
                                if k in lp})
            else:
                self.layers_expert.append(
                    {"w1": lp["w1"], "w3": lp["w3"], "w2": lp["w2"]})
        self.head = {k: params[k] for k in ("embed", "final_norm", "lm_head")
                     if k in params}

        # ---- placement ----------------------------------------------------
        rep_a = NamedSharding(self.attn_mesh, P())
        self.layers_attn = jax.device_put(self.layers_attn, rep_a)
        self.head = jax.device_put(self.head, rep_a)
        if cfg.moe is not None:
            ep_shard = NamedSharding(self.expert_mesh, P("ep"))
            self.expert_in_spec = P("ep")       # (E, C, d) sharded by expert
        else:
            ep_shard = {"w1": NamedSharding(self.expert_mesh, P(None, "ep")),
                        "w3": NamedSharding(self.expert_mesh, P(None, "ep")),
                        "w2": NamedSharding(self.expert_mesh, P("ep", None))}
            self.expert_in_spec = P()           # (T, d) replicated (TP FFN)
        # the un-placed (E, ...) expert weights: live rebalances
        # (apply_placement) regather per-node virtual-slot copies from them
        self.layers_expert = [
            jax.device_put(le, ep_shard) for le in self.layers_expert]
        # the M2N path computes routing on the expert shards (replicated
        # over "ep"), so each MoE layer's router (and optional logit
        # bias) also lives on that mesh
        self.layers_router_ep: List[Optional[dict]] = [None] * cfg.n_layers
        if cfg.moe is not None and plan.use_m2n:
            rep_e = NamedSharding(self.expert_mesh, P())
            self.layers_router_ep = [jax.device_put(rp, rep_e)
                                     for rp in routers]

        # ---- live expert placement (§6) ----------------------------------
        # placement starts out static (contiguous expert blocks); the
        # serving engine may re-solve it from live routing counts and
        # apply_placement() a replicated layout without changing shapes
        self.placement: Optional[lb_lib.Placement] = None
        self.tables: Optional[lb_lib.PlacementTables] = None
        self.layers_expert_placed: Optional[List[dict]] = None
        self._tables_dev = None
        self._tables_dev_ep = None
        self._active_slots: Optional[jax.Array] = None
        if cfg.moe is not None:
            e_loc = -(-cfg.moe.n_experts // self.n_expert_nodes)
            self.placement_slots = min(
                cfg.moe.n_experts,
                max(e_loc, int(round(e_loc * plan.replication_slots))))
        else:
            self.placement_slots = 0

        self.obs = obs.Recorder()       # disagg.<stage> spans
        self.reset_expert_counts()
        self.last_trace: List[tuple] = []
        # stage -> (jitted program, args) of its latest call; read only
        # by compiled_stages()
        self._last_calls: dict = {}
        self._build_jits()

    @property
    def kv_sharding(self) -> NamedSharding:
        """Placement migrated KV rows should land on: the attention
        group owns the KV cache (per-request rows, replicated here —
        the dp sharding of a single row is degenerate)."""
        return NamedSharding(self.attn_mesh, P())

    # ------------------------------------------------------------------ jits
    def _build_jits(self):
        cfg = self.cfg
        rep_e = NamedSharding(self.expert_mesh, P())

        @jax.named_scope("attn")
        def attn_phase(p, x, act, cache, pos, window, tbl=None):
            delta, new_cache = self_attn_decode_sublayer(
                p, cfg, x, pos, cache, window,
                use_kernels=self.plan.use_kernels)
            x = x + delta
            h = rms_norm(x, p["ln2"])
            if cfg.moe is None or self.plan.use_m2n:
                # m2n: routing+dispatch happen on the expert shards; only
                # the (T, d) activations cross the wire
                return x, h, new_cache, None
            cap = moe_lib.expert_capacity(h.shape[0], cfg.moe,
                                          self.plan.capacity_mode)
            if tbl is None:
                n_buckets = cfg.moe.n_experts
                spn = n_buckets
            else:
                # live placement: route each (token, k) to one replica of
                # its expert — a virtual slot id in the node-major
                # (N*S, ...) gathered weight layout.  Same expert
                # weights, same combine → token-identical output.
                n_buckets = self.n_expert_nodes * self.placement_slots
                spn = self.placement_slots
            if self.plan.use_kernels:
                # fused Pallas router+top-k+dispatch (act = live-row
                # weights keeps idle KV rows out of the traffic trace)
                from repro.kernels import ops as kops
                tk = {} if tbl is None else {
                    "rep_node": tbl["rep_node"],
                    "rep_slot": tbl["rep_slot"],
                    "rep_cum": tbl["rep_cum"]}
                idx_buf, gate_buf, counts = kops.gating_dispatch(
                    h, p["router"], cfg.moe.top_k, n_buckets=n_buckets,
                    capacity=cap, bias=p.get("router_bias"),
                    count_weights=act, slots_per_node=spn, **tk)
            else:
                routing = moe_lib.route(h, p["router"], cfg.moe.top_k,
                                        p.get("router_bias"))
                # idle KV rows are decoded anyway (static batch shape) but
                # must not pollute the live traffic trace
                counts = moe_lib.routing_counts(routing, cfg.moe.n_experts,
                                                act)
                if tbl is not None:
                    vslot, _ = moe_lib.replica_assign(
                        routing.experts, tbl["rep_node"], tbl["rep_slot"],
                        tbl["rep_cum"],
                        slots_per_node=self.placement_slots)
                    routing = moe_lib.Routing(routing.gates, vslot,
                                              routing.probs)
                idx_buf, gate_buf = moe_lib.dispatch_indices(
                    routing, n_buckets, cap)
            xe = h.at[idx_buf].get(mode="fill", fill_value=0)  # (E, C, d)
            return x, h, new_cache, {"xe": xe, "idx": idx_buf,
                                     "gates": gate_buf, "counts": counts}

        @jax.named_scope("expert")
        def expert_phase_moe(pe, xe):
            if self.plan.use_kernels:
                from repro.kernels import ops as kops
                return per_device(
                    self.expert_mesh, P("ep"),
                    lambda pe, xe: kops.grouped_mlp(
                        xe, pe["we1"], pe["we3"], pe["we2"], cfg.act))(pe, xe)
            h = moe_lib.activation(jnp.einsum("ecd,edf->ecf", xe, pe["we1"]),
                                   cfg.act)
            h = h * jnp.einsum("ecd,edf->ecf", xe, pe["we3"])
            return jnp.einsum("ecf,efd->ecd", h, pe["we2"])

        @jax.named_scope("expert")
        def expert_phase_dense(pe, h):
            return gated_ffn(h, pe["w1"], pe["w3"], pe["w2"], cfg.act)

        @jax.named_scope("expert")
        def expert_phase_m2n(pe, router_p, h, act, tbl=None):
            if tbl is not None:
                tbl = dict(tbl, slots_per_node=self.placement_slots)
            y, _aux, counts = m2n_lib.sharded_routed_experts(
                dict(pe, **router_p), h, cfg.moe, cfg.act,
                self.plan.capacity_mode, mesh=self.expert_mesh,
                data_axes=(), expert_axis="ep", tables=tbl,
                with_counts=True, count_weights=act,
                use_kernels=self.plan.use_kernels)
            return y, counts

        def combine_tail(p, x, h, y):
            if "ws1" in p:   # shared experts stay with attention (dense)
                shared = gated_ffn(h, p["ws1"], p["ws3"], p["ws2"], cfg.act)
                g = jax.nn.sigmoid(h.astype(jnp.float32)
                                   @ p["shared_gate"].astype(jnp.float32))
                y = y + (g[:, None] * shared.astype(jnp.float32)).astype(x.dtype)
            if "wd1" in p:   # arctic dense residual
                y = y + gated_ffn(h, p["wd1"], p["wd3"], p["wd2"], cfg.act)
            if cfg.use_post_norm:
                y = rms_norm(y, p["ln2_post"])
            return x + y

        @jax.named_scope("combine")
        def combine_phase(p, x, h, out, idx_buf, gate_buf):
            T, d = x.shape
            y = jnp.zeros((T, d), jnp.float32)
            w = out.astype(jnp.float32) * gate_buf[..., None]
            y = y.at[idx_buf.reshape(-1)].add(w.reshape(-1, d), mode="drop")
            return combine_tail(p, x, h, y.astype(x.dtype))

        @jax.named_scope("combine")
        def combine_m2n(p, x, h, y):
            # y: (T, d) routed output, already gate-weighted and combined
            # on the expert shards
            return combine_tail(p, x, h, y)

        @jax.named_scope("combine")
        def combine_dense(p, x, out):
            if cfg.use_post_norm:
                out = rms_norm(out, p["ln2_post"])
            return x + out

        def embed(head, tokens):
            return _embed_tokens(head, cfg, tokens)

        def lm_head(head, x):
            return _lm_head(head, cfg, x)

        def attn_program(window, placed):
            if placed:
                fn = (lambda p, tbl, x, a, c, pos:
                      attn_phase(p, x, a, c, pos, window, tbl))
            else:
                fn = (lambda p, x, a, c, pos:
                      attn_phase(p, x, a, c, pos, window))
            if self.plan.use_kernels:
                # the attention group computes replicated; its kernels
                # run once per device
                fn = per_device(self.attn_mesh, P(), fn)
            return jax.jit(fn)

        self._attn_phase = {w: attn_program(w, False)
                            for w in {0, cfg.window}}
        # placed variants thread the placement lookup tables through the
        # dispatch; traced lazily on the first rebalanced decode step
        self._attn_phase_placed = {w: attn_program(w, True)
                                   for w in {0, cfg.window}}
        ein = NamedSharding(self.expert_mesh, self.expert_in_spec)
        if cfg.moe is not None and self.plan.use_m2n:
            # tokens arrive replicated on the expert mesh; the shard_map
            # inside does the only wire traffic (the combine psum)
            ein = rep_e
            self._expert_phase = jax.jit(expert_phase_m2n,
                                         out_shardings=rep_e)
            self._expert_phase_placed = jax.jit(
                lambda pe, rp, tbl, h, a: expert_phase_m2n(pe, rp, h, a,
                                                           tbl),
                out_shardings=rep_e)
        elif cfg.moe is not None:
            self._expert_phase = jax.jit(expert_phase_moe,
                                         in_shardings=(None, ein),
                                         out_shardings=ein)
        else:
            self._expert_phase = jax.jit(expert_phase_dense,
                                         in_shardings=(None, ein),
                                         out_shardings=rep_e)
        self._combine = jax.jit(combine_phase)
        self._combine_m2n = jax.jit(combine_m2n)
        self._combine_dense = jax.jit(combine_dense)
        self._embed = jax.jit(embed)
        self._lm_head = jax.jit(lm_head)
        self._expert_sharding = ein
        self._attn_rep = NamedSharding(self.attn_mesh, P())

    # ------------------------------------------------------------ transport
    def _send_m2n(self, payload):
        """M2N dispatch hop onto the expert group.  Baseline path:
        (E, C, d) capacity buffers scattered expert-major (wire bytes =
        payload); m2n path: raw (T, d) activations replicated to every
        expert node (wire bytes = payload x N)."""
        fanout = (self.n_expert_nodes
                  if self.cfg.moe is not None and self.plan.use_m2n else 1)
        return self.transport.send_tokens(payload, self._expert_sharding,
                                          fanout=fanout).data

    def _send_n2m(self, out):
        """N2M return hop back onto the attention group."""
        return self.transport.send_tokens(out, self._attn_rep).data

    def _account_combine(self, t_tokens: int, d_model: int, itemsize: int):
        """Account the combine psum inside the m2n shard_map — the only
        wire traffic of that dispatch scheme.  It executes inside jit,
        so its analytically known bytes go through the transport's
        collective side-channel (reduce-scatter + all-gather over the
        expert axis: 2 * T * d * (N-1)/N)."""
        n = self.n_expert_nodes
        if n > 1:
            nbytes = 2 * t_tokens * d_model * itemsize * (n - 1) // n
            self.transport.record_collective(nbytes, fanout=n)

    # ----------------------------------------------- live expert placement
    def apply_placement(self, placement: lb_lib.Placement):
        """Install a (possibly replicated) expert placement in the live
        serving path (paper §6).

        The fractional ``Placement`` is compiled to lookup tables under
        this instance's fixed per-node slot budget
        (``placement_slots``), and every MoE layer's expert weights are
        regathered node-major into (N*S, ...) virtual-slot arrays on the
        expert mesh — replicated hot experts occupy one slot per hosting
        node.  Shapes are placement-independent, so repeated rebalances
        swap array contents without recompiling, and token routing stays
        deterministic (replica choice hashes the token index), keeping
        outputs token-identical to the static placement.

        Returns True when the placement was installed, False when the
        solved tables match the ones already being served (steady
        state) and the regather/upload was skipped."""
        if self.cfg.moe is None:
            raise ValueError("expert placement needs an MoE config")
        if self.plan.capacity_mode != "full":
            # bounded capacity is priced per dispatch bucket: splitting a
            # replicated expert over several buckets changes which tokens
            # overflow vs the static path, so the token-identity guarantee
            # only holds for the drop-free serving capacity
            raise ValueError(
                f"live placement requires capacity_mode='full' (drop-free); "
                f"got {self.plan.capacity_mode!r}")
        tables = lb_lib.placement_tables(placement, self.placement_slots)
        if tables.n_nodes != self.n_expert_nodes:
            raise ValueError(f"placement solved for {tables.n_nodes} nodes, "
                             f"runtime has {self.n_expert_nodes}")
        if self._placement_unchanged(tables):
            # steady state: same slot layout and (near-)same traffic
            # split — skip the full per-layer weight regather/upload, the
            # dominant cost of frequent rebalance intervals
            return False
        flat = tables.slot_experts.reshape(-1)
        gather = jnp.asarray(np.where(flat < 0, 0, flat), jnp.int32)
        ep_shard = NamedSharding(self.expert_mesh, P("ep"))
        # the node-major (N*S, ...) weight regather is a transport hop
        # (every MoE layer's virtual-slot copies uploaded in one send) —
        # per-hop bytes/latency land under the "weights" kind
        self.layers_expert_placed = self.transport.regather_weights(
            [{k: raw[k][gather] for k in EXPERT_KEYS}
             for raw in self.layers_expert],
            ep_shard).data
        tbl = {"rep_node": jnp.asarray(tables.rep_node),
               "rep_slot": jnp.asarray(tables.rep_slot),
               "rep_cum": jnp.asarray(tables.rep_cum)}
        # the baseline path reads the tables on the attention side (the
        # router runs there); the m2n path reads them on the expert mesh
        self._tables_dev = jax.device_put(
            tbl, NamedSharding(self.attn_mesh, P()))
        self._tables_dev_ep = jax.device_put(
            tbl, NamedSharding(self.expert_mesh, P()))
        self.placement = placement
        self.tables = tables
        return True

    def _placement_unchanged(self, tables: lb_lib.PlacementTables,
                             cum_tol: float = 0.05) -> bool:
        """True when ``tables`` would serve (essentially) the placement
        already installed: identical expert->slot layout and replica
        traffic splits within ``cum_tol``.  Any placement is output-
        correct, so keeping a split that moved by <tol is free — it only
        leaves the traffic shares marginally stale."""
        cur = self.tables
        return (cur is not None
                and np.array_equal(cur.slot_experts, tables.slot_experts)
                and np.array_equal(cur.rep_node, tables.rep_node)
                and np.array_equal(cur.rep_slot, tables.rep_slot)
                and np.abs(cur.rep_cum - tables.rep_cum).max() <= cum_tol)

    @property
    def placement_fractions(self) -> np.ndarray:
        """Effective (M, N) expert->node fractions the runtime serves:
        the applied placement's post-repair fractions, or the static
        contiguous-block layout before any rebalance."""
        if self.tables is not None:
            return self.tables.fractions
        E = self.cfg.moe.n_experts
        return lb_lib.static_placement(E, self.n_expert_nodes).fractions

    # ------------------------------------------------------ routing counts
    def set_active_slots(self, active):
        """Mark which KV slots currently serve a request ((B,) 0/1).

        The engine decodes every slot each iteration (static batch
        shape); the mask keeps idle rows out of the accumulated routing
        counts so the load balancer solves for real traffic only.
        ``None`` restores the default (count every row)."""
        self._active_slots = (None if active is None
                              else jnp.asarray(active, jnp.float32))

    def reset_expert_counts(self):
        """Zero the accumulated per-expert routed-token counts."""
        E = self.cfg.moe.n_experts if self.cfg.moe is not None else 0
        # separate accumulators per source mesh (attention-side routing
        # in the baseline path, expert-shard routing under m2n) so the
        # lazy per-layer adds never force a cross-mesh transfer
        self._counts_attn = jnp.zeros((E,), jnp.float32)
        self._counts_ep = jnp.zeros((E,), jnp.float32)

    def peek_expert_counts(self) -> np.ndarray:
        """Per-expert routed-token counts since the last reset (blocks
        on the device accumulators)."""
        return (np.asarray(self._counts_attn, np.float64)
                + np.asarray(self._counts_ep, np.float64))

    def take_expert_counts(self) -> np.ndarray:
        """``peek_expert_counts`` + reset — one sliding-window interval
        of live expert traffic for ``balance_experts``."""
        counts = self.peek_expert_counts()
        self.reset_expert_counts()
        return counts

    # ------------------------------------------------------- stage timing
    def reset_stage_times(self):
        """Zero the cumulative per-stage wall-clock accounting."""
        self.obs.reset()

    def _timed(self, stage: str, mb: int, layer: int, fn, *args):
        """Run one pipeline stage of micro-batch ``mb`` at ``layer``
        inside the span ``disagg.<stage>``.

        Non-profiling mode measures host issue time only (the pipeline
        stays fully async); ``plan.profile_stages`` blocks on the result
        so the numbers reflect device execution (and serialise the
        pipeline — use for measurement, not serving)."""
        with self.obs.span(f"disagg.{stage}", mb=mb, layer=layer):
            out = fn(*args)
            if self.plan.profile_stages:
                jax.block_until_ready(out)
        if hasattr(fn, "lower"):
            self._last_calls[stage] = (fn, args)
        return out

    def compiled_stages(self) -> dict:
        """Optimized HLO text of each jitted stage program, compiled for
        the arguments of its latest call — the programs the last decode
        step ran (the M2N/N2M hops are transport puts, not programs)."""
        return {stage: fn.lower(*args).compile().as_text()
                for stage, (fn, args) in self._last_calls.items()}

    def stage_report(self) -> dict:
        """Cumulative per-stage seconds/counts plus the paper's per-op
        T_a / T_e / T_c estimates (attention-side compute, expert
        compute, one communication hop)."""
        tot = self.obs.totals()
        n_of = {s: tot.get(f"disagg.{s}", (0, 0.0))[0] for s in STAGES}
        s_of = {s: tot.get(f"disagg.{s}", (0, 0.0))[1] for s in STAGES}
        rep = {f"{s}_s": s_of[s] for s in STAGES}
        rep.update({f"{s}_n": n_of[s] for s in STAGES})
        rep["t_a"] = (s_of["attn"] + s_of["combine"]) / max(1, n_of["attn"])
        rep["t_e"] = s_of["expert"] / max(1, n_of["expert"])
        rep["t_c"] = (s_of["m2n"] + s_of["n2m"]) / max(
            1, n_of["m2n"] + n_of["n2m"])
        return rep

    def measure_stage_times(self, batch: int, max_seq: int = 32) -> dict:
        """Profile one decode iteration on a throwaway cache and return
        ``stage_report()`` with device-accurate stage times."""
        tokens = jnp.zeros((batch,), jnp.int32)
        pos = jnp.zeros((batch,), jnp.int32)
        cache = init_cache(self.cfg, batch, max_seq,
                           self.head["embed"].dtype)
        prev = self.plan.profile_stages
        self.plan.profile_stages = True
        try:
            self.decode_step(tokens, cache, pos)   # warm-up: jit compiles
            self.reset_stage_times()
            logits, _ = self.decode_step(tokens, cache, pos)
            jax.block_until_ready(logits)
            report = self.stage_report()
        finally:
            self.plan.profile_stages = prev
            self.reset_stage_times()
        return report

    def auto_microbatches(self, batch: int, *, max_m: Optional[int] = None,
                          max_seq: int = 32) -> int:
        """Measured-T_a/T_e/T_c choice of m (paper eq. 3 feasibility)."""
        rep = self.measure_stage_times(batch, max_seq)
        return pingpong.choose_microbatches(rep["t_a"], rep["t_e"],
                                            rep["t_c"], max_m=max_m)

    # ------------------------------------------------------------- decoding
    def decode_step(self, tokens: jax.Array, cache: dict, pos: jax.Array):
        """One decode iteration for the global batch with ping-pong
        micro-batching.  tokens/pos: (B,).  cache: monolithic cache pytree
        (as built by models.init_cache).  Returns (logits, new_cache)."""
        return self.decode_microbatched(tokens, cache, pos)

    def decode_microbatched(self, tokens: jax.Array, cache: dict,
                            pos: jax.Array,
                            mb_slices: Optional[Sequence[slice]] = None):
        """Schedule-driven ping-pong decode.

        Executes ``pingpong.build_schedule(m, L)`` with double-buffered
        stages: after attn(mb)+dispatch are issued on the attention mesh
        and expert(mb) on the expert mesh, the *previous* micro-batch's
        return hop + combine are issued — so at any moment one micro-batch
        occupies each compute group and JAX async dispatch overlaps them
        (the paper's fig. 4 shuttle).  ``mb_slices`` lets the serving
        engine pin micro-batches to its KV-slot groups; default is a
        near-even split into ``plan.n_microbatches``.

        The issue order is recorded in ``self.last_trace`` (comparable to
        ``build_schedule``/simulator events) and per-stage wall time is
        accumulated for ``stage_report()``."""
        cfg = self.cfg
        B = tokens.shape[0]
        if mb_slices is None:
            mbs = pingpong.even_partition(B, self.plan.n_microbatches)
        else:
            mbs = [s for s in mb_slices if s.stop > s.start]
            if [s.start for s in mbs] != [0] + [s.stop for s in mbs[:-1]] \
                    or (mbs and mbs[-1].stop != B):
                raise ValueError(f"micro-batch slices {mbs} must cover "
                                 f"[0, {B}) contiguously")
        trace = []

        xs = [self._embed(self.head, tokens[s]) for s in mbs]
        poss = [pos[s] for s in mbs]
        # active-slot mask (set_active_slots): engine-marked live rows;
        # idle KV slots decode anyway but are masked out of the traffic
        # trace.  Default: every row counts (standalone decode_step use)
        act = (self._active_slots if self._active_slots is not None
               else jnp.ones((B,), jnp.float32))
        acts = [act[s] for s in mbs]
        # per-(mb, layer) cache entries are indexed lazily below

        placed = self.layers_expert_placed is not None
        new_cache_entries = [[None] * cfg.n_layers for _ in mbs]
        for l in range(cfg.n_layers):
            kind = _layer_kind(cfg, l)
            window = cfg.window if kind == "local" else 0
            pa = self.layers_attn[l]
            pe = (self.layers_expert_placed[l] if placed
                  else self.layers_expert[l])
            inflight: deque = deque()

            def drain_one():
                i, x, h, out, disp = inflight.popleft()
                out_back = self._timed(                        # N2M return
                    "n2m", i, l, self._send_n2m, out)
                if cfg.moe is not None and self.plan.use_m2n:
                    xs[i] = self._timed("combine", i, l, self._combine_m2n,
                                        pa, x, h, out_back)
                elif cfg.moe is not None:
                    xs[i] = self._timed("combine", i, l, self._combine, pa,
                                        x, h, out_back, disp["idx"],
                                        disp["gates"])
                else:
                    xs[i] = self._timed("combine", i, l, self._combine_dense,
                                        pa, x, out_back)

            for i, s in enumerate(mbs):
                entry = self._cache_entry(cache, l, s)
                if placed and not self.plan.use_m2n:
                    x, h, new_entry, disp = self._timed(
                        "attn", i, l, self._attn_phase_placed[window], pa,
                        self._tables_dev, xs[i], acts[i], entry, poss[i])
                else:
                    x, h, new_entry, disp = self._timed(
                        "attn", i, l, self._attn_phase[window], pa, xs[i],
                        acts[i], entry, poss[i])
                if disp is not None and "counts" in disp:
                    # lazy device add — the live traffic trace for the
                    # engine's periodic §6 rebalance; never blocks
                    self._counts_attn = self._counts_attn + disp["counts"]
                new_cache_entries[i][l] = new_entry
                trace.append(("attn", i, l))
                # M2N dispatch hop: routed capacity buffers in the
                # baseline path, raw (T, d) activations in the m2n path
                payload = h if disp is None else disp["xe"]
                buf = self._timed("m2n", i, l, self._send_m2n, payload)
                if cfg.moe is not None and self.plan.use_m2n:
                    if placed:
                        out, cnt = self._timed(
                            "expert", i, l, self._expert_phase_placed, pe,
                            self.layers_router_ep[l], self._tables_dev_ep,
                            buf, acts[i])
                    else:
                        out, cnt = self._timed(
                            "expert", i, l, self._expert_phase, pe,
                            self.layers_router_ep[l], buf, acts[i])
                    self._counts_ep = self._counts_ep + cnt
                    self._account_combine(payload.shape[0], payload.shape[1],
                                          payload.dtype.itemsize)
                else:
                    out = self._timed("expert", i, l, self._expert_phase, pe,
                                      buf)
                trace.append(("expert", i, l))
                inflight.append((i, x, h, out, disp))
                # double buffer: one micro-batch computing on the expert
                # group, one returning/combining on the attention group
                if len(inflight) > 1:
                    drain_one()
            while inflight:
                drain_one()

        logits = jnp.concatenate([self._lm_head(self.head, x) for x in xs], 0)
        new_cache = self._merge_cache(cache, new_cache_entries, mbs)
        self.last_trace = trace
        return logits, new_cache

    # ------------------------------------------------------------- plumbing
    def _cache_entry(self, cache, l, s):
        where, pos_i, blk = _layer_index(self.cfg, l)
        if where == "block":
            entry = jax.tree.map(lambda a: a[blk], cache["blocks"][pos_i])
        else:
            entry = cache["remainder"][pos_i]
        return jax.tree.map(lambda a: a[s], entry)

    def _merge_cache(self, cache, new_entries, mbs):
        cfg = self.cfg
        cache = jax.tree.map(lambda a: a, cache)  # shallow copy pytree
        blocks = [jax.tree.map(lambda a: a, b) for b in cache["blocks"]]
        remainder = list(cache["remainder"])
        for l in range(cfg.n_layers):
            where, pos_i, blk = _layer_index(cfg, l)
            for i, s in enumerate(mbs):
                upd = new_entries[i][l]
                if where == "block":
                    blocks[pos_i] = jax.tree.map(
                        lambda full, part: full.at[blk, s].set(part),
                        blocks[pos_i], upd)
                else:
                    remainder[pos_i] = jax.tree.map(
                        lambda full, part: full.at[s].set(part),
                        remainder[pos_i], upd)
        return {"blocks": tuple(blocks), "remainder": tuple(remainder)}
