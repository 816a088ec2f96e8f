"""Serving launcher: run the continuous-batching engine over the
monolithic decode path, the disaggregated (MegaScale-Infer) runtime, or
the full ping-pong micro-batched pipeline — optionally with prefill
disaggregated onto its own device cluster (``--prefill-devices``) and
explicit KV migration into the decode cache.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b \
      --reduced --runtime pingpong --requests 16 --microbatches auto
  PYTHONPATH=src python -m repro.launch.serve --reduced \
      --runtime pingpong --prefill-devices 1 --transfer async
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import chip_share, get_config, reduced
from repro.core.disagg import STAGES, DisaggPlan, DisaggregatedInstance
from repro.core.transport import HOP_KINDS, make_transport
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import split_serving_devices
from repro.models import init_params
from repro.serving.config import RUNTIMES, ServingConfig
from repro.serving.engine import Engine, Request
from repro.serving.prefill import PrefillWorker


def _format_stages(report: dict) -> str:
    per_stage = " ".join(
        f"{s}={report[f'{s}_s'] * 1e3:.1f}ms/{report[f'{s}_n']}"
        for s in STAGES)
    return (f"stages: {per_stage} | per-op t_a={report['t_a'] * 1e6:.0f}us "
            f"t_e={report['t_e'] * 1e6:.0f}us t_c={report['t_c'] * 1e6:.0f}us")


def _format_phases(ph: dict) -> str:
    return (f"phases: prefill={ph['prefill_s'] * 1e3:.1f}ms/"
            f"{ph['prefills']} "
            f"transfer[{ph['transfer_mode']}]={ph['transfer_s'] * 1e3:.1f}ms/"
            f"{ph['transfer_n']} "
            f"decode={ph['decode_s'] * 1e3:.1f}ms/{ph['decode_n']}")


def _format_counters(c: dict) -> str:
    steps = max(1, c["decode_steps"])
    return (f"counters: host_syncs={c['host_syncs'] / steps:.1f}/step "
            f"programs_built={c['programs_built'] / steps:.2f}/step "
            f"({c['programs_built']} built, {c['programs_from_cache']} "
            f"from the persistent cache) admissions={c['admissions']}")


def _format_transport(tr: dict) -> str:
    parts = []
    for kind in HOP_KINDS:
        h = tr.get(kind)
        if h and h["hops"]:
            p = f"{kind}={h['bytes'] / 1e6:.2f}MB/{h['hops']}"
            if h["sim_s"]:
                p += f"~{h['sim_s'] * 1e3:.1f}ms"
            parts.append(p)
    return f"transport[{tr['backend']}]: " + (" ".join(parts) or "no hops")


def zipf_router_bias(n_experts: int, alpha: float,
                     scale: float = 1.5) -> jax.Array:
    """A (E,) additive router-logit bias that skews expert selection
    toward low-index experts following a zipf(alpha) popularity curve —
    the controlled stand-in for the real-traffic routing skew the
    paper's §6 load balancer absorbs.  ``scale`` trades skew strength
    against the per-token logit noise (bias is centered log-popularity,
    so scale ~ a few logit standard deviations gives a heavy but not
    degenerate skew)."""
    ranks = np.arange(1, n_experts + 1, dtype=np.float64)
    p = ranks ** -alpha
    p /= p.sum()
    bias = np.log(p)
    bias = (bias - bias.mean()) * scale / max(1e-9, bias.std())
    return jnp.asarray(bias, jnp.float32)


def _inject_router_bias(params: dict, cfg, bias: jax.Array) -> dict:
    """Attach a router-logit bias to every MoE layer in-place (the
    serving paths read the optional ``router_bias`` key next to
    ``router``)."""
    n = 0
    for pos, _kind in enumerate(cfg.block_pattern):
        lp = params["blocks"][pos]
        if "router" in lp:
            lp["router_bias"] = jnp.broadcast_to(bias,
                                                 (cfg.n_blocks,) + bias.shape)
            n += 1
    for pos, _kind in enumerate(cfg.remainder_pattern):
        lp = params["remainder"][pos]
        if "router" in lp:
            lp["router_bias"] = bias
            n += 1
    if not n:
        raise ValueError(f"{cfg.name} has no MoE router to bias")
    return params


def run(arch: Optional[str] = None, *,
        config: Optional[ServingConfig] = None, **overrides):
    """Serve one workload described by a ``ServingConfig``.

    Call styles::

        run(config=ServingConfig(arch=..., runtime="pingpong", ...))
        run("mixtral-8x22b", runtime="pingpong", n_requests=16)

    Every keyword is a ``ServingConfig`` field (the legacy kwargs call
    style maps 1:1 onto fields); explicit kwargs override ``config``.

    ``prompt_len`` > 0 pins every request's prompt length (one prefill
    shape to compile — benchmarks use this to keep timing variance down);
    0 draws lengths in [2, max_seq/4).  ``warmup_requests`` > 0 serves
    that many throwaway requests through the engine first, so jit/eager
    compiles (per fresh runtime instance — the m2n shard_map alone costs
    seconds) never land in the measured wall time; reported tokens /
    decode_iters / prefills / transport hops and tok/s cover the
    measured batch only.

    ``expert_rebalance_every`` > 0 re-solves expert placement from live
    routing counts every N decode iterations (replicating hot experts
    unless ``expert_replication=False``); ``zipf_route_bias`` > 0
    injects a zipf(alpha) router-logit bias — the skewed-routing
    scenario the rebalancer exists to absorb.

    ``transport`` selects the M2N transport backend every token/KV/
    weight hop goes through (``core.transport``): "inproc" (the
    single-process device_put path), "simrdma" (same movement + an
    alpha-beta RDMA latency model per hop), or "multi"
    (``jax.distributed`` multi-controller).

    ``n_layers`` > 0 serves the config cut to that depth, every width
    kept (with ``use_reduced=False``: the published widths);
    ``lead_layers``, ``experts_held`` and ``vocab`` cut it further to one
    chip's share (``config.chip_share``); ``dtype``
    is the weights' and KV cache's dtype.  Parameters are initialised
    in one jitted program, so each weight is drawn and cast in place
    (no float32 copy of a bfloat16 model).

    Returns the measured stats; the served ``Engine`` rides along under
    ``"engine"`` for callers that inspect it after the run (its
    ``first_logits``, its runtime's compiled phases)."""
    if arch is not None:
        overrides.setdefault("arch", arch)
    sc = (ServingConfig(**overrides) if config is None
          else config.with_overrides(**overrides))
    cfg = get_config(sc.arch)
    if sc.use_reduced:
        cfg = reduced(cfg)
    cfg = chip_share(cfg, n_layers=sc.n_layers, lead_layers=sc.lead_layers,
                     experts_held=sc.experts_held, vocab=sc.vocab)
    dtype = jnp.dtype(sc.dtype)
    params = jax.jit(init_params, static_argnums=(0, 2))(
        cfg, jax.random.PRNGKey(sc.seed), dtype)
    if sc.zipf_route_bias > 0.0:
        if cfg.moe is None:
            raise ValueError("--zipf-route-bias needs an MoE arch")
        params = _inject_router_bias(
            params, cfg, zipf_router_bias(cfg.moe.n_experts,
                                          sc.zipf_route_bias))

    # one transport ledger for every hop of the run: M2N/N2M token
    # shuttles, KV migration, live-placement weight regathers
    transport = make_transport(sc.transport)

    # cluster topology: prefill group (optional) vs decode group; the
    # decode group is further split attention/expert by the runtime
    prefill_devs, decode_devs = split_serving_devices(sc.prefill_devices)
    if sc.verbose and prefill_devs:
        disjoint = not set(map(id, prefill_devs)) & set(map(id, decode_devs))
        note = "disjoint" if disjoint else "overlapping, single-device fallback"
        print(f"prefill cluster: {len(prefill_devs)} device(s), decode "
              f"cluster: {len(decode_devs)} device(s) ({note})")

    engine_kw = {}
    inst = None
    if sc.runtime in ("disagg", "pingpong"):
        m = 2 if sc.microbatches == "auto" else int(sc.microbatches)
        inst = DisaggregatedInstance(
            cfg, params, devices=decode_devs,
            plan=DisaggPlan(n_microbatches=m, use_m2n=sc.use_m2n,
                            use_kernels=sc.use_kernels,
                            profile_stages=sc.profile_stages),
            transport=transport)
        if sc.microbatches == "auto":
            # measure T_a/T_e/T_c on a profiled decode iteration, then
            # apply the paper's m >= 2(1 + T_c/T_f) feasibility bound
            m = inst.auto_microbatches(sc.max_batch, max_m=sc.max_batch)
            inst.plan.n_microbatches = m
            if sc.verbose:
                print(f"auto-selected m={m} micro-batches")
    if sc.runtime == "disagg":
        # runtime handle rides along so live expert rebalancing (and the
        # imbalance report in stats()) work without the pingpong engine
        engine_kw.update(decode_fn=inst.decode_step, runtime=inst)
    elif sc.runtime == "pingpong":
        engine_kw.update(runtime=inst)
    if sc.expert_rebalance_every and inst is None:
        raise ValueError("--expert-rebalance-every needs "
                         "--runtime disagg|pingpong")

    if inst is not None:
        # the attention group owns the KV cache
        engine_kw.update(kv_sharding=inst.kv_sharding)
    if prefill_devs:
        engine_kw.update(
            prefill_worker=PrefillWorker(
                cfg, params, prefill_devs, max_seq=sc.max_seq,
                chunk_tokens=sc.prefill_chunk_tokens,
                page_size=sc.page_size if sc.kv_layout == "paged" else 0))

    eng = Engine(cfg, params, config=sc, transport=transport, dtype=dtype,
                 **engine_kw)
    rng = np.random.RandomState(sc.seed)
    # shared-system-prompt workload: every request opens with the same
    # ``shared_prefix_len`` tokens (the pattern the radix prefix cache
    # deduplicates) followed by a per-request random suffix
    shared_prefix = (rng.randint(2, cfg.vocab,
                                 size=sc.shared_prefix_len).tolist()
                     if sc.shared_prefix_len else [])

    def make_prompt(plen: int) -> list:
        if shared_prefix:
            if plen <= len(shared_prefix):
                raise ValueError(f"prompt_len {plen} must exceed "
                                 f"shared_prefix_len {len(shared_prefix)}")
            tail = rng.randint(2, cfg.vocab,
                               size=plen - len(shared_prefix)).tolist()
            return shared_prefix + tail
        return rng.randint(2, cfg.vocab, size=plen).tolist()

    if sc.warmup_requests:
        for i in range(sc.warmup_requests):
            plen = sc.prompt_len or 8
            eng.submit(Request(rid=-1 - i, prompt=make_prompt(plen),
                               max_new_tokens=2))
        eng.run_until_done()
    pre = eng.stats()
    for i in range(sc.n_requests):
        plen = sc.prompt_len or int(rng.randint(2, sc.max_seq // 4))
        eng.submit(Request(rid=i, prompt=make_prompt(plen),
                           max_new_tokens=sc.max_new))
    t0 = time.perf_counter()
    eng.run_until_done()
    dt = time.perf_counter() - t0
    stats = eng.stats()
    for k in ("tokens", "decode_iters", "prefills", "finished"):
        stats[k] -= pre[k]
    if sc.warmup_requests:  # latency over measured requests only — warmup
        lat = [r.t_done - r.t_submit  # latencies include compile time
               for r in eng.finished if r.rid >= 0]
        stats["mean_latency_s"] = sum(lat) / len(lat) if lat else 0.0
    # phase breakdown must cover the measured batch only, or warmup
    # compile time dominates the attribution (cumulative keys only —
    # transfer_mode/prefill_devices are not counters)
    for k in ("prefill_s", "prefills", "prefill_batches", "prefill_tokens",
              "transfer_s", "transfer_n", "decode_s", "decode_n"):
        if k in stats["phases"]:
            stats["phases"][k] -= pre["phases"].get(k, 0)
    for k in stats["counters"]:
        stats["counters"][k] -= pre["counters"][k]
    for k in ("rebalances", "placement_updates", "rebalance_s"):
        if k in stats:
            stats[k] -= pre.get(k, 0)
    # transport hop counters are cumulative per kind, same treatment
    pre_tr = pre.get("transport", {})
    for kind, hop in stats.get("transport", {}).items():
        if isinstance(hop, dict) and kind in pre_tr:
            for k in hop:
                hop[k] -= pre_tr[kind].get(k, 0)
    # prefix-cache counters are cumulative too (warmup may legitimately
    # seed the radix tree — only the measured phase's hits count)
    if "prefix_cache" in stats:
        pre_px = pre.get("prefix_cache", {})
        for k in ("hits", "misses", "hit_tokens", "evictions", "inserts"):
            stats["prefix_cache"][k] -= pre_px.get(k, 0)
        tot = stats["prefix_cache"]["hits"] + stats["prefix_cache"]["misses"]
        stats["prefix_cache"]["hit_rate"] = (
            stats["prefix_cache"]["hits"] / tot if tot else 0.0)
    if "kv_pages" in stats:
        for k in ("allocs", "forks", "released"):
            stats["kv_pages"][k] -= pre.get("kv_pages", {}).get(k, 0)
    stats["wall_s"] = dt
    stats["decode_tok_per_s"] = stats["tokens"] / dt
    stats["engine"] = eng
    if sc.verbose:
        print(f"{sc.arch} [{sc.runtime}"
              f"{'+disagg-prefill' if prefill_devs else ''}] served "
              f"{stats['finished']} requests, "
              f"{stats['tokens']} tokens in {dt:.2f}s "
              f"({stats['decode_tok_per_s']:.1f} tok/s, "
              f"{stats['decode_iters']} decode iters)")
        print(_format_phases(stats["phases"]))
        print(_format_counters(stats["counters"]))
        print(_format_transport(stats["transport"]))
        if "kv_pages" in stats:
            kp = stats["kv_pages"]
            line = (f"kv[paged]: {kp['used']}/{kp['n_pages']} pages of "
                    f"{kp['page_size']} (high-water {kp['high_water']}, "
                    f"{kp['allocs']} allocs, {kp['forks']} COW forks)")
            if "prefix_cache" in stats:
                px = stats["prefix_cache"]
                line += (f" | prefix: {px['hits']} hits / {px['misses']} "
                         f"misses ({px['hit_tokens']} tokens reused, "
                         f"{px['evictions']} evicted)")
            print(line)
        if "stages" in stats:
            print(_format_stages(stats["stages"]))
        if "imbalance" in stats:
            costs = " ".join(f"{c:.0f}" for c in stats["expert_node_cost"])
            print(f"experts: imbalance={stats['imbalance']:.2f} "
                  f"node-cost=[{costs}] "
                  f"rebalances={stats['rebalances']} "
                  f"replicated={stats['replicated_experts']}")
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="model config name (default mixtral-8x22b; the "
                         "default is only accepted together with "
                         "--reduced — full-scale params don't fit a "
                         "local host)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="serve the config cut to this many layers, every "
                         "width kept (0 = the config's own depth)")
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="dtype of the weights and the KV cache")
    ap.add_argument("--runtime", default="monolithic", choices=RUNTIMES)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--microbatches", default="3",
                    help="micro-batch count, or 'auto' to pick m from "
                         "measured T_a/T_e/T_c (paper eq. 3)")
    ap.add_argument("--use-m2n", action="store_true",
                    help="route MoE layers through the shard_map M2N "
                         "dispatch (core.m2n) on the expert mesh")
    ap.add_argument("--kernels", action="store_true", dest="use_kernels",
                    help="run the decode hot path on the Pallas kernels "
                         "(flash decode attention, fused gating+dispatch, "
                         "grouped expert MLP); compiled on TPU, "
                         "interpret mode off-TPU")
    ap.add_argument("--prefill-devices", type=int, default=0,
                    help="reserve N devices as a dedicated prefill "
                         "cluster (0 = inline prefill on the decode "
                         "cluster); KV rows are migrated into the decode "
                         "cache at admission")
    ap.add_argument("--transfer", default="async", choices=("sync", "async"),
                    help="KV migration mode: async overlaps the copy "
                         "with in-flight decode, sync blocks per row")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=512,
                    help="token budget per batched prefill call on the "
                         "prefill cluster")
    ap.add_argument("--profile-stages", action="store_true",
                    help="block per stage for device-accurate timings "
                         "(serialises the pipeline)")
    ap.add_argument("--expert-rebalance-every", type=int, default=0,
                    help="re-solve expert placement from live routing "
                         "counts every N decode iterations (0 = static "
                         "contiguous placement; needs --runtime "
                         "disagg|pingpong)")
    ap.add_argument("--expert-replication",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="allow hot experts to be replicated across "
                         "expert nodes when rebalancing (paper §6 "
                         "on-device redundancy)")
    ap.add_argument("--zipf-route-bias", type=float, default=0.0,
                    help="inject a zipf(alpha) router-logit bias to "
                         "skew expert traffic (benchmark scenario for "
                         "the load balancer; 0 = off)")
    ap.add_argument("--transport", default="inproc",
                    choices=("inproc", "simrdma", "multi"),
                    help="M2N transport backend every token/KV/weight "
                         "hop goes through (see docs/transport.md): "
                         "inproc = single-process device_put, simrdma = "
                         "same movement + per-hop RDMA cost model, "
                         "multi = jax.distributed multi-controller "
                         "(coordinator/rank from REPRO_* env vars)")
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=("contiguous", "paged"),
                    help="KV-cache layout: contiguous = one (B, W) ring-"
                         "buffer row per request; paged = block tables "
                         "over a refcounted fixed-size page pool "
                         "(serving.pages) with radix prefix reuse")
    ap.add_argument("--page-size", type=int, default=16,
                    help="token slots per KV page (paged layout; must "
                         "divide --max-seq)")
    ap.add_argument("--kv-pool-pages", type=int, default=0,
                    help="page-pool size (0 = auto from "
                         "max_batch/max_seq)")
    ap.add_argument("--prefix-cache",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="radix prefix cache over the page pool: "
                         "requests sharing a prompt prefix reuse its KV "
                         "pages instead of recomputing (paged layout "
                         "only)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="workload knob: every prompt opens with the "
                         "same N tokens (shared-system-prompt scenario; "
                         "0 = fully random prompts)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="pin every prompt to this length (0 = random)")
    ap.add_argument("--warmup-requests", type=int, default=0,
                    help="throwaway requests served first so jit "
                         "compiles stay out of the measured wall time")
    args = ap.parse_args()
    if args.arch is None and not args.reduced:
        ap.error("pass --arch, or --reduced to serve the default "
                 "mixtral-8x22b at reduced scale")
    enable_compile_cache()
    run(config=ServingConfig.from_args(args))


if __name__ == "__main__":
    main()
