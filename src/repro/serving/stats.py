"""Versioned schema for ``Engine.stats()``.

``Engine.stats()`` used to be a free-form dict whose keys drifted PR to
PR; the serve_bench perf gate diagnosed drift by dumping raw dict keys.
This module pins the schema: ``EngineStats`` is the typed shape of the
payload, and ``STATS_SCHEMA_VERSION`` is bumped on every breaking change
(key removed/renamed/retyped — additive keys do not bump it).  The
version rides inside every stats payload and inside the committed
``BENCH_serve.json``, so the gate's schema-drift messages can say
"baseline is schema v2, code emits v3" instead of listing keys.

Version history:
  1  (implicit) — pre-transport payloads: core counters + phases +
     stages + the §6 expert-balance report, no version field.
  2  — adds ``schema_version`` itself and the per-hop ``transport``
     section (per-kind hops/bytes/issue_s/sim_s from ``core.transport``).
  3  — ``use_kernels`` joins the core payload (always present, so perf
     baselines distinguish the Pallas hot path from the jnp path; a
     semantic addition every entry must carry, hence the bump).
  4  — ``kv_layout`` joins the core payload (always present — paged and
     contiguous runs are different memory systems and must never be
     compared silently), plus the optional ``kv_pages`` (page-pool
     occupancy/high-water) and ``prefix_cache`` (radix hit/miss/evict)
     sections for paged engines.
"""
from __future__ import annotations

from typing import List, TypedDict

STATS_SCHEMA_VERSION = 4


class PhaseStats(TypedDict, total=False):
    """Per-phase host-issue wall time (prefill / KV transfer / decode)."""
    prefill_s: float
    prefills: int
    prefill_batches: int
    prefill_tokens: int
    prefill_devices: int
    transfer_s: float
    transfer_n: int
    transfer_mode: str
    decode_s: float
    decode_n: int


class CounterStats(TypedDict):
    """Cumulative counts of the engine's work (``Engine.counters``)."""
    host_syncs: int
    decode_steps: int
    admissions: int
    programs_built: int
    programs_from_cache: int


class TransportHopStats(TypedDict):
    """One hop kind's cumulative counters (see ``core.transport``)."""
    hops: int
    bytes: int
    issue_s: float
    sim_s: float


class TransportStats(TypedDict, total=False):
    """Per-hop-kind transport accounting; ``backend`` names the backend
    ('inproc' | 'simrdma' | 'multi').  Kind keys appear only once that
    kind has traffic."""
    backend: str
    tokens: TransportHopStats
    kv: TransportHopStats
    weights: TransportHopStats
    collective: TransportHopStats


class PagePoolStats(TypedDict):
    """Page-pool accounting (``serving.pages.PagePool.stats``)."""
    n_pages: int
    page_size: int
    used: int
    free: int
    reserved: int
    high_water: int
    utilization: float
    allocs: int
    forks: int
    released: int


class PrefixCacheStats(TypedDict):
    """Radix prefix-cache counters (``serving.prefix_cache``)."""
    hits: int
    misses: int
    hit_rate: float
    hit_tokens: int
    evictions: int
    inserts: int
    nodes: int


class EngineStats(TypedDict, total=False):
    """The stable shape of ``Engine.stats()``.

    Keys marked optional appear only for the matching engine setup
    (ping-pong stages, MoE balance report, transport section)."""
    schema_version: int
    finished: int
    tokens: int
    decode_iters: int
    prefills: int
    mean_latency_s: float
    mode: str
    use_kernels: bool
    disagg_prefill: bool
    kv_layout: str
    phases: PhaseStats
    counters: CounterStats
    # paged KV layout only (schema v4+)
    kv_pages: PagePoolStats
    prefix_cache: PrefixCacheStats
    # ping-pong runtime only
    n_microbatches: int
    stages: dict
    # transport layer (schema v2+)
    transport: TransportStats
    # live expert balance report (MoE + disagg runtime only)
    imbalance: float
    expert_node_cost: List[float]
    expert_loads: List[float]
    rebalances: int
    placement_updates: int
    rebalance_s: float
    replicated_experts: int
