"""Continuous-batching serving engine.

Iteration-level scheduling (Orca [72]): between decode iterations,
finished requests leave the batch and waiting requests are prefilled into
their slots.  The decode iteration itself runs in one of two modes:

  * ``monolithic`` — one batched ``models.decode_step`` (or any
    ``decode_fn``) over all KV slots per iteration;
  * ``pingpong`` — the paper's runtime: KV slots are partitioned into m
    contiguous micro-batch groups and each iteration is executed by a
    ``core.disagg.DisaggregatedInstance`` through the ping-pong schedule
    (attention and expert stages double-buffered across disjoint device
    groups).  Slot recycling stays at micro-batch granularity: each group
    sheds finished requests and prefills waiting ones into its freed
    slots between iterations, while other groups' device work is still in
    flight (JAX async dispatch) — admission never stalls the pipeline.

Prefill and decode are separate phases, and — the paper's §3 split —
optionally separate *clusters*: with a ``prefill_worker``
(``serving.prefill.PrefillWorker``) waiting requests are prefilled on
the prefill device group and ``_admit()`` consumes completed
``(first_token, request_kv)`` handles from the worker's transfer queue,
migrating each request's KV rows onto the decode placement
(``kvcache.migrate_kv``) instead of running ``models.prefill`` inline on
the decode cluster's devices.  Admission order equals submission order
in both paths, so under greedy sampling the disaggregated engine is
token-for-token identical to the inline-prefill engine.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig
from repro.core.load_balance import balance_experts, evaluate_placement
from repro.core.transport import InProcessTransport
from repro.models import decode_step, init_cache, prefill
from repro.models.moe import routed_rows
from repro.models.stubs import extra_inputs
from repro.serving.config import ServingConfig
from repro.serving.kvcache import (MicrobatchSlotAllocator, SlotAllocator,
                                   mb_slot_ranges, migrate_kv, migrate_pages,
                                   reset_row, write_row)
from repro.serving.pages import PagePool, n_pages_for
from repro.serving.prefill import suffix_prefill
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampler import SamplingParams, sample, sample_rows
from repro.serving.stats import (STATS_SCHEMA_VERSION, CounterStats,
                                  EngineStats)

# sentinel distinguishing "kwarg not passed" from an explicit value, so
# the deprecated scalar aliases below can coexist with ``config=``
_UNSET = object()

# inline admission's prefill: one program per (config, prompt length,
# max_seq), so the layer scan is traced once, not at every admission
_prefill = jax.jit(prefill, static_argnames=("cfg", "max_seq",
                                             "capacity_mode"))


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    generated: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    t_submit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0

    @property
    def done(self) -> bool:
        if len(self.generated) >= self.max_new_tokens:
            return True
        return bool(self.generated and self.eos_id is not None
                    and self.generated[-1] == self.eos_id)

    @property
    def position(self) -> int:
        return len(self.prompt) + len(self.generated)


class Engine:
    # scalar kwargs that moved into ServingConfig; still accepted as
    # deprecated aliases for one release (``mode`` maps onto
    # ``ServingConfig.runtime``)
    _DEPRECATED_SCALARS = ("max_batch", "max_seq", "mode", "transfer",
                           "seed", "expert_rebalance_every",
                           "expert_replication", "expert_window")

    def __init__(self, cfg: ModelConfig, params: dict, *,
                 config: Optional[ServingConfig] = None,
                 max_batch=_UNSET, max_seq=_UNSET, dtype=jnp.float32,
                 sampling: Optional[SamplingParams] = None,
                 decode_fn: Optional[Callable] = None,
                 mode=_UNSET, runtime=None,
                 n_microbatches: Optional[int] = None,
                 prefill_worker=None, transfer=_UNSET,
                 kv_sharding=None, seed=_UNSET,
                 expert_rebalance_every=_UNSET,
                 expert_replication=_UNSET,
                 expert_window=_UNSET,
                 transport=None, page_pool=None, prefix_cache=None):
        """``config``: the canonical way to set every scalar knob — a
        ``serving.config.ServingConfig``.  The scalar kwargs listed in
        ``_DEPRECATED_SCALARS`` are deprecated aliases kept for one
        release; when passed they override the config and emit a
        ``DeprecationWarning``.  Object wiring (``runtime``,
        ``prefill_worker``, ``transport``, ``sampling``, ``decode_fn``,
        ``kv_sharding``, ``dtype``, ``n_microbatches``) stays keyword-
        based — those are instances the launcher owns.

        mode "monolithic": decode via ``decode_fn`` (default: batched
        ``models.decode_step``; pass ``runtime.decode_step`` for the
        disaggregated path without engine-level micro-batching).

        mode "pingpong": decode via ``runtime`` (a
        ``core.disagg.DisaggregatedInstance``) with the engine's KV slots
        split into ``n_microbatches`` groups (default: the runtime plan's
        m, clamped to ``max_batch``) shuttled through the ping-pong
        schedule.

        ``prefill_worker`` (a ``serving.prefill.PrefillWorker``) moves
        prefill onto its own device cluster: admission consumes the
        worker's transfer queue and ``migrate_kv`` reshards each
        request's KV rows onto ``kv_sharding`` (default: wherever the
        decode cache lives — pass the runtime's ``kv_sharding`` to pin
        rows to the attention group).  ``transfer`` is "async" (the
        copy overlaps in-flight decode via JAX async dispatch) or
        "sync" (block on each migrated row before admission).

        ``expert_rebalance_every`` > 0 turns on live expert
        load-balanced placement (paper §6): every that many decode
        iterations the engine drains the runtime's per-expert routing
        counts, re-solves ``core.load_balance.balance_experts`` over a
        sliding window of the last ``expert_window`` intervals, and
        applies the placement (hot experts replicated across expert
        nodes when ``expert_replication``) to the runtime.  Token
        routing across replicas is deterministic (token-index hash), so
        rebalanced serving stays token-identical under greedy
        sampling."""
        legacy = {k: v for k, v in (
            ("max_batch", max_batch), ("max_seq", max_seq), ("mode", mode),
            ("transfer", transfer), ("seed", seed),
            ("expert_rebalance_every", expert_rebalance_every),
            ("expert_replication", expert_replication),
            ("expert_window", expert_window)) if v is not _UNSET}
        base = (config if config is not None
                else ServingConfig(max_batch=8, max_seq=256))
        if legacy:
            warnings.warn(
                f"Engine({', '.join(sorted(legacy))}=...) scalar kwargs "
                f"are deprecated; pass config=ServingConfig(...) instead",
                DeprecationWarning, stacklevel=2)
            mode_alias = legacy.pop("mode", None)
            if mode_alias is not None:
                if mode_alias not in ("monolithic", "pingpong"):
                    raise ValueError(f"unknown engine mode {mode_alias!r}")
                legacy["runtime"] = mode_alias
            base = base.with_overrides(**legacy)
        self.serving_config = base
        mode = base.engine_mode
        max_batch, max_seq = base.max_batch, base.max_seq
        transfer, seed = base.transfer, base.seed
        expert_rebalance_every = base.expert_rebalance_every
        expert_replication = base.expert_replication
        expert_window = base.expert_window
        if sampling is None:
            sampling = base.sampling_params()
        if mode == "pingpong":
            if runtime is None:
                raise ValueError("pingpong mode needs a DisaggregatedInstance"
                                 " runtime")
            if decode_fn is not None:
                raise ValueError("pingpong mode drives the runtime directly;"
                                 " decode_fn is not used")
        if expert_rebalance_every:
            if runtime is None or not hasattr(runtime, "apply_placement"):
                raise ValueError("expert_rebalance_every needs a runtime "
                                 "with live placement support "
                                 "(core.disagg.DisaggregatedInstance)")
            if cfg.moe is None:
                raise ValueError("expert rebalancing needs an MoE config")
            if getattr(runtime.plan, "capacity_mode", "full") != "full":
                # fail at construction, not mid-serve at the first
                # rebalance (apply_placement enforces the same invariant)
                raise ValueError("expert rebalancing requires the runtime "
                                 "plan's capacity_mode='full' (drop-free)")
        self.cfg = cfg
        self.params = params
        # one transport ledger for the whole serving path: prefer the
        # runtime's (so m2n/n2m/weights hops and the engine's KV hops
        # land in the same stats), else the explicit one, else in-process
        if transport is None:
            transport = getattr(runtime, "transport", None)
        self.transport = transport if transport is not None \
            else InProcessTransport()
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.sampling = sampling
        self.mode = mode
        self.runtime = runtime
        # KV layout: contiguous (one dense (B, W) ring-buffer row per
        # slot) or paged (rows are virtual — per-request block tables
        # over a refcounted page pool; the dense view is gathered per
        # decode step and the new token scattered back, so the decode
        # computation itself is layout-agnostic and token-identical)
        self.kv_layout = base.kv_layout
        if self.kv_layout == "paged":
            self.page_pool = page_pool if page_pool is not None else PagePool(
                cfg, n_pages=base.n_pool_pages, page_size=base.page_size,
                max_seq=max_seq, dtype=dtype)
            if prefix_cache is not None:
                self.prefix = prefix_cache
            else:
                self.prefix = (PrefixCache(self.page_pool)
                               if base.prefix_cache else None)
            self.cache = None           # gathered from the pool per step
            self.block_tables: Dict[int, List[int]] = {}   # rid -> pages
            self._page_reserve: Dict[int, int] = {}        # rid -> unspent
        else:
            self.page_pool = None
            self.prefix = None
            self.cache = init_cache(cfg, max_batch, max_seq, dtype)
            if kv_sharding is not None:
                self.cache = jax.device_put(self.cache, kv_sharding)
        # paged disaggregated prefill shares one pool/prefix tree with
        # the worker (single-process: the transport hop still prices the
        # page movement onto the decode placement)
        if self.page_pool is not None and prefill_worker is not None \
                and getattr(prefill_worker, "page_size", 0):
            if prefill_worker.page_pool is None:
                prefill_worker.page_pool = self.page_pool
            if prefill_worker.prefix_cache is None and self.prefix is not None:
                prefill_worker.prefix_cache = self.prefix
        if mode == "pingpong":
            m = n_microbatches or runtime.plan.n_microbatches
            self.mb_slices = mb_slot_ranges(max_batch, m)
            self.slots = MicrobatchSlotAllocator(max_batch, self.mb_slices)
        else:
            self.mb_slices = None
            self.slots = SlotAllocator(max_batch)
        self.waiting: List[Request] = []
        self.running: Dict[int, Request] = {}
        self.finished: List[Request] = []
        self.key = jax.random.PRNGKey(seed)
        # whether any decode path runs on the Pallas kernels: either the
        # config asked for them (monolithic decode_step) or the runtime
        # plan was built with them (pingpong / m2n)
        self.use_kernels = bool(
            base.use_kernels
            or getattr(getattr(runtime, "plan", None), "use_kernels", False))
        # decode_fn(tokens, cache, pos) -> (logits, new_cache)
        self._decode = decode_fn or (
            lambda toks, cache, pos: decode_step(
                self.params, cfg, toks, cache, pos,
                use_kernels=base.use_kernels))
        # rows the routed-expert matmuls of one default decode call
        # compute (its capacity is drop-free, C = max_batch), counted
        # from static shapes as ``expert_rows``
        self._expert_rows = (
            routed_rows(cfg.moe, max_batch, "full") * cfg.n_moe_layers
            if cfg.moe is not None and decode_fn is None
            and mode == "monolithic" else 0)
        self._last_token = np.zeros((max_batch,), np.int32)
        # logits (B, V) of the first and the latest decode iteration:
        # every runtime decodes the same first step from the same
        # prefill, so first_logits is what runtime-parity checks compare
        self.first_logits: Optional[jax.Array] = None
        self.last_logits: Optional[jax.Array] = None
        self.n_decode_iters = 0
        self.n_prefills = 0
        self.prefill_worker = prefill_worker
        self.transfer = transfer
        self.kv_sharding = kv_sharding
        # host spans (engine.*) and counters of the scheduler
        self.obs = obs.Recorder()
        # live expert load balancing (paper §6)
        self.expert_rebalance_every = expert_rebalance_every
        self.expert_replication = expert_replication
        self._load_window: deque = deque(maxlen=max(1, expert_window))
        self.n_rebalances = 0
        self.n_placement_updates = 0
        self._track_experts = (cfg.moe is not None and runtime is not None
                               and hasattr(runtime, "set_active_slots"))

    # ------------------------------------------------------------- frontend
    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        self.waiting.append(req)

    # ------------------------------------------------------------- schedule
    def _start_request(self, req: Request, slot: int, last_logits):
        """Shared admission bookkeeping: sample the first token (engine
        PRNG stream — identical order in inline and disaggregated paths)
        and mark the request running."""
        req.slot = slot
        self.key, k = jax.random.split(self.key)
        tok = int(sample(last_logits, k, self.sampling)[0])
        self.obs.count("host_syncs")
        req.generated.append(tok)
        req.t_first_token = time.perf_counter()
        self._last_token[slot] = tok
        self.running[req.rid] = req
        self.n_prefills += 1
        self.obs.count("admissions")

    # --------------------------------------------------------- paged helpers
    def _pages_for_request(self, req: Request) -> int:
        """Worst-case pages a request can ever touch: its prompt plus
        all generated tokens, clamped at the ring-buffer width (wrapped
        writes land in already-owned pages — or fork shared ones, which
        the clamp also covers since every logical page is counted)."""
        n_slots = min(self.max_seq, len(req.prompt) + req.max_new_tokens)
        return n_pages_for(n_slots, self.page_pool.page_size)

    def _reserve_pages(self, rid: int, n: int) -> bool:
        """OOM-safe admission: reserve the request's worst case up
        front, evicting cold prefix-cache pages if the free list is
        short.  On False the request stays waiting (head-of-line — FIFO
        admission order is part of the parity contract)."""
        if not self.page_pool.reserve(n):
            if self.prefix is None:
                return False
            self.prefix.evict(n - self.page_pool.available)
            if not self.page_pool.reserve(n):
                return False
        self._page_reserve[rid] = n
        return True

    def _take_page(self, rid: int) -> int:
        """Allocate one page against the request's reservation."""
        left = self._page_reserve.get(rid, 0)
        if left > 0:
            self._page_reserve[rid] = left - 1
            return self.page_pool.alloc(from_reserve=True)
        return self.page_pool.alloc()

    def _fork_page(self, rid: int, page: int) -> int:
        """Copy-on-write a shared page, spending reservation if any."""
        left = self._page_reserve.get(rid, 0)
        if left > 0:
            self._page_reserve[rid] = left - 1
            return self.page_pool.fork(page, from_reserve=True)
        return self.page_pool.fork(page)

    def _install_pages(self, req: Request, shared: List[int],
                       fresh: List[int]):
        """Final admission bookkeeping shared by the inline and
        disaggregated paged paths: the block table owns one reference
        per page (the lookup pin for shared pages, the alloc reference
        for fresh ones) and full prompt pages are published to the
        radix tree."""
        table = list(shared) + list(fresh)
        self.block_tables[req.rid] = table
        if self.prefix is not None:
            self.prefix.insert(req.prompt, table)

    def _admit_paged(self):
        """Inline paged admission: prefix-aware prefill straight into
        freshly allocated pages.  A radix hit gathers the shared pages
        and computes only the suffix (decode starts at the fork point).
        """
        ps = self.page_pool.page_size
        while self.waiting and self.slots.free:
            req = self.waiting[0]
            h, shared = ((self.prefix.lookup(req.prompt)
                          if self.prefix is not None else (0, [])))
            needed = self._pages_for_request(req) - len(shared)
            if not self._reserve_pages(req.rid, needed):
                for p in shared:        # drop the lookup pins
                    self.page_pool.release(p)
                break
            self.waiting.pop(0)
            slot = self.slots.alloc(req.rid)
            ids = {"rid": req.rid, "prompt_len": len(req.prompt)}
            with self.obs.span("engine.prefill", **ids):
                if h:
                    row = self.page_pool.gather_row(shared)
                    last_logits, row = suffix_prefill(
                        self.params, self.cfg, req.prompt, row, h)
                else:
                    toks = jnp.asarray([req.prompt], jnp.int32)
                    extras = extra_inputs(self.cfg, 1)
                    last_logits, row = prefill(self.params, self.cfg, toks,
                                               max_seq=self.max_seq,
                                               **extras)
            with self.obs.span("engine.insert", **ids):
                n_written = n_pages_for(len(req.prompt), ps)
                fresh = [self._take_page(req.rid)
                         for _ in range(n_written - len(shared))]
                if fresh:
                    self.page_pool.write_row_span(
                        fresh, row, len(shared) * ps, len(req.prompt))
            self._install_pages(req, shared, fresh)
            self._start_request(req, slot, last_logits)

    def _admit_paged_from_transfer_queue(self):
        """Disaggregated paged admission: the worker emits per-page
        chunks; only the non-shared pages cross the prefill->decode
        boundary (``kvcache.migrate_pages``, one "kv" hop per page)."""
        w = self.prefill_worker
        while self.waiting:
            w.submit(self.waiting.pop(0))
        lookahead = len(self.slots.free) + self.max_batch
        while w.pending_count and w.ready_count < lookahead:
            w.pump(max_batches=1)
        while self.slots.free and w.ready_count:
            res = w.pop()
            req = res.request
            shared = list(res.shared_pages)
            needed = self._pages_for_request(req) - len(shared)
            if not self._reserve_pages(req.rid, needed):
                w.ready.appendleft(res)     # keep FIFO order; retry later
                break
            slot = self.slots.alloc(req.rid)
            fresh = [self._take_page(req.rid)
                     for _ in range(len(res.page_chunks))]
            with self.obs.span("engine.insert", rid=req.rid,
                               prompt_len=len(req.prompt)):
                migrate_pages(self.page_pool, res.page_chunks, fresh,
                              sharding=self.kv_sharding,
                              sync=self.transfer == "sync",
                              transport=self.transport)
            self._install_pages(req, shared, fresh)
            self._start_request(req, slot, res.last_logits)

    def _admit(self):
        if self.kv_layout == "paged":
            if self.prefill_worker is not None:
                self._admit_paged_from_transfer_queue()
            else:
                self._admit_paged()
            return
        if self.prefill_worker is not None:
            self._admit_from_transfer_queue()
            return
        while self.waiting and self.slots.free:
            req = self.waiting.pop(0)
            slot = self.slots.alloc(req.rid)
            ids = {"rid": req.rid, "prompt_len": len(req.prompt)}
            with self.obs.span("engine.prefill", **ids):
                toks = jnp.asarray([req.prompt], jnp.int32)
                extras = extra_inputs(self.cfg, 1)
                last_logits, rcache = _prefill(self.params, self.cfg, toks,
                                               self.max_seq, **extras)
            with self.obs.span("engine.insert", **ids):
                self.cache = write_row(self.cache, rcache, slot)
            self._start_request(req, slot, last_logits)

    def _admit_from_transfer_queue(self):
        """Disaggregated prefill (paper §3): feed the prefill cluster the
        whole waiting queue (queueing is free — no KV is materialized
        until a batch is pumped), run prefill batches with bounded
        work-ahead, then admit completed prefills from the transfer
        queue into free KV slots, migrating each request's KV rows onto
        the decode placement.  Work-ahead past slot availability is
        sound (prefill results depend only on the prompt) but capped at
        one extra batch-width of ready handles, so a request burst
        cannot pile up unbounded per-request KV on the prefill cluster
        (backpressure: more is pumped as slots free up each step)."""
        w = self.prefill_worker
        while self.waiting:
            w.submit(self.waiting.pop(0))
        lookahead = len(self.slots.free) + self.max_batch
        while w.pending_count and w.ready_count < lookahead:
            w.pump(max_batches=1)
        while self.slots.free and w.ready_count:
            res = w.pop()
            req = res.request
            slot = self.slots.alloc(req.rid)
            with self.obs.span("engine.insert", rid=req.rid,
                               prompt_len=len(req.prompt)):
                self.cache = migrate_kv(self.cache, res.kv, slot,
                                        sharding=self.kv_sharding,
                                        sync=self.transfer == "sync",
                                        transport=self.transport)
            self._start_request(req, slot, res.last_logits)

    def _rebalance(self):
        """Drain one interval of live routing counts, re-solve placement
        over the sliding window, and apply it to the runtime (§6)."""
        self._load_window.append(self.runtime.take_expert_counts())
        self.obs.count("host_syncs")            # the counts read back
        loads = np.sum(self._load_window, axis=0)
        placement = balance_experts(
            loads, self.runtime.n_expert_nodes,
            allow_replication=self.expert_replication)
        if self.runtime.apply_placement(placement):
            self.n_placement_updates += 1
        self.n_rebalances += 1

    def _retire(self):
        for rid in [r for r, q in self.running.items() if q.done]:
            req = self.running.pop(rid)
            req.t_done = time.perf_counter()
            slot = self.slots.release(rid)
            if self.kv_layout == "paged":
                # drop the table's references; pages the radix tree (or
                # another request) still holds stay alive — everything
                # else returns to the free list.  No reset needed: a
                # recycled page is invalidated (pos = -1) on alloc.
                for p in self.block_tables.pop(rid):
                    self.page_pool.release(p)
                left = self._page_reserve.pop(rid, 0)
                if left:
                    self.page_pool.unreserve(left)
            else:
                # invalidate the freed KV row before any reuse: a
                # recycled slot must never expose the previous
                # request's cache state
                self.cache = reset_row(self.cache, self.cfg, slot,
                                       self.max_seq)
            self.finished.append(req)

    def _paged_writeback(self, dense_cache):
        """Scatter this iteration's newly written KV token per live row
        back into its physical page (one batched scatter per leaf).

        The decode step wrote each row's token at ring slot
        ``(position - 1) % W`` of the gathered dense view; the page
        holding that slot is grown lazily from the request's
        reservation, and forked first if it is shared (copy-on-write:
        ring-buffer wrap is the one legal write into a prefix-cache /
        multi-holder page)."""
        pool, ps = self.page_pool, self.page_pool.page_size
        rows, slots, pages, offs = [], [], [], []
        for req in self.running.values():
            w = (req.position - 1) % self.max_seq
            lp = w // ps
            tb = self.block_tables[req.rid]
            if lp == len(tb):
                tb.append(self._take_page(req.rid))
            elif pool.is_shared(tb[lp]):
                tb[lp] = self._fork_page(req.rid, tb[lp])
            rows.append(req.slot)
            slots.append(w)
            pages.append(tb[lp])
            offs.append(w % ps)
        pool.write_tokens(dense_cache, np.asarray(rows, np.int32),
                          np.asarray(slots, np.int32),
                          np.asarray(pages, np.int32),
                          np.asarray(offs, np.int32))

    # ----------------------------------------------------------------- step
    def step(self) -> int:
        """One engine iteration: admit + one decode step.  Returns number
        of active requests decoded."""
        with self.obs.span("engine.step", step=self.n_decode_iters):
            return self._step()

    def _step(self) -> int:
        # in pingpong mode, micro-batch-granular recycling lives in the
        # allocator: released slots return to their own group's free list
        # and admission refills the emptiest group — host-side work that
        # overlaps whatever device work is still in flight
        span = self.obs.span
        with span("engine.retire"):
            self._retire()
        with span("engine.admit"):
            self._admit()
        if not self.running:
            return 0
        with span("engine.prepare"):
            # every per-row input is built on the host in one pass over
            # the running rows and crosses to the device as one array
            # each; free slots decode at position 0
            paged = self.kv_layout == "paged"
            pos = np.zeros((self.max_batch,), np.int32)
            active = np.zeros((self.max_batch,), np.float32)
            if paged:
                bt = np.full((self.max_batch, self.page_pool.n_logical), -1,
                             np.int32)
            for req in self.running.values():
                pos[req.slot] = req.position - 1
                active[req.slot] = 1.0
                if paged:
                    tb = self.block_tables[req.rid]
                    bt[req.slot, :len(tb)] = tb
            # a copy: sampling rewrites _last_token while this step may
            # still be in flight
            toks = jnp.asarray(self._last_token.copy())
            pos = jnp.asarray(pos)
            if self._track_experts:
                # only live rows feed the routing-count traffic trace
                self.runtime.set_active_slots(active)
            if paged:
                # block-table gather: materialize the dense (B, W) view
                # the decode step expects.  The gather is a pure copy
                # (unmapped pages read as pos=-1, exactly a reset row), so
                # the decode computation below is bit-identical to the
                # contiguous layout's across all runtimes and kernels.
                cache = self.page_pool.gather(bt)
            else:
                cache = self.cache
        with span("engine.decode"):
            if self.mode == "pingpong":
                logits, cache = self.runtime.decode_microbatched(
                    toks, cache, pos, self.mb_slices)
            else:
                logits, cache = self._decode(toks, cache, pos)
                if self._expert_rows:
                    self.obs.count("expert_rows", self._expert_rows)
        if self.kv_layout == "paged":
            with span("engine.writeback"):
                self._paged_writeback(cache)
        else:
            self.cache = cache
        if self.first_logits is None:
            self.first_logits = logits
        self.last_logits = logits
        with span("engine.sample"):
            self.key, k = jax.random.split(self.key)
            # per-request key folding: sampled tokens must not depend on
            # which KV row a request occupies (engines pack rows
            # differently)
            rids = np.zeros((self.max_batch,), np.int64)
            for req in self.running.values():
                rids[req.slot] = req.rid
            # one read of the whole batch's tokens
            nxt = np.asarray(sample_rows(logits, k, rids, self.sampling))
            for req in self.running.values():
                tok = int(nxt[req.slot])
                req.generated.append(tok)
                self._last_token[req.slot] = tok
        n_active = len(self.running)
        self.obs.count("host_syncs")
        self.obs.count("decode_steps")
        self.n_decode_iters += 1
        if (self.expert_rebalance_every
                and self.n_decode_iters % self.expert_rebalance_every == 0):
            with span("engine.rebalance"):
                self._rebalance()
        with span("engine.retire"):
            self._retire()
        return n_active

    @property
    def outstanding(self) -> bool:
        """Any request not yet finished — waiting, running, or still in
        the prefill cluster's pending/transfer queues."""
        w = self.prefill_worker
        backlog = bool(w is not None and (w.pending_count or w.ready_count))
        return bool(self.waiting or self.running or backlog)

    def run_until_done(self, max_iters: int = 10_000):
        while self.outstanding and max_iters:
            self.step()
            max_iters -= 1
        return self.finished

    # ------------------------------------------------------------- metrics
    def counters(self) -> CounterStats:
        """Cumulative counts of the scheduler's work: blocking
        device-to-host reads, decode steps, admissions, and the programs
        JAX built (``programs_from_cache`` of them loaded from the
        persistent cache) inside the spans of the engine, its runtime or
        its prefill worker."""
        c = self.obs.counters()
        out = {k: c.get(k, 0)
               for k in ("host_syncs", "decode_steps", "admissions")}
        recs = [x.obs for x in (self, self.runtime, self.prefill_worker)
                if hasattr(x, "obs")]
        for k in (obs.BUILT, obs.FROM_CACHE):
            out[k] = sum(r.counters().get(k, 0) for r in recs)
        return out

    def stats(self) -> EngineStats:
        lat = [r.t_done - r.t_submit for r in self.finished]
        toks = sum(len(r.generated) for r in self.finished)
        out = {
            "schema_version": STATS_SCHEMA_VERSION,
            "finished": len(self.finished),
            "tokens": toks,
            "decode_iters": self.n_decode_iters,
            "prefills": self.n_prefills,
            "mean_latency_s": sum(lat) / len(lat) if lat else 0.0,
            "mode": self.mode,
            "use_kernels": self.use_kernels,
            "disagg_prefill": self.prefill_worker is not None,
            "kv_layout": self.kv_layout,
        }
        if self.page_pool is not None:
            out["kv_pages"] = self.page_pool.stats()
        if self.prefix is not None:
            out["prefix_cache"] = self.prefix.stats()
        # per-phase breakdown from the engine's span totals (host-issue
        # wall time: the pipeline stays async — prefill/transfer overlap
        # in-flight decode)
        tot = self.obs.totals()
        phases = {"transfer_s": self.obs.seconds("engine.insert"),
                  "transfer_n": tot.get("engine.insert", (0,))[0],
                  "transfer_mode": self.transfer,
                  "decode_s": (self.obs.seconds("engine.decode")
                               + self.obs.seconds("engine.writeback")),
                  "decode_n": self.n_decode_iters}
        if self.prefill_worker is not None:
            phases.update(self.prefill_worker.stats())
        else:
            phases.update(prefill_s=self.obs.seconds("engine.prefill"),
                          prefills=self.n_prefills)
        out["phases"] = phases
        out["counters"] = self.counters()
        # per-hop wire traffic, by kind (tokens / kv / weights /
        # collective) — the transport ledger shared with the runtime
        out["transport"] = self.transport.stats()
        if self.mode == "pingpong":
            out["n_microbatches"] = len(self.mb_slices)
            out["stages"] = self.runtime.stage_report()
        if (self.cfg.moe is not None and self.runtime is not None
                and hasattr(self.runtime, "placement_fractions")):
            # live expert-balance report: the placement the runtime is
            # serving right now, priced on the latest traffic window
            # (counts drained at rebalances plus the not-yet-drained
            # remainder — also covers the never-rebalanced static case)
            loads = (np.sum(self._load_window, axis=0)
                     if self._load_window else 0.0)
            loads = loads + self.runtime.peek_expert_counts()
            pl = evaluate_placement(self.runtime.placement_fractions, loads)
            out["imbalance"] = pl.imbalance
            out["expert_node_cost"] = pl.node_cost.tolist()
            out["expert_loads"] = loads.tolist()
            out["rebalances"] = self.n_rebalances
            out["placement_updates"] = self.n_placement_updates
            out["rebalance_s"] = self.obs.seconds("engine.rebalance")
            n_replicas = (self.runtime.placement_fractions > 1e-9).sum(axis=1)
            out["replicated_experts"] = int((n_replicas > 1).sum())
        return out
