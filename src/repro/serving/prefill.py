"""Prefill cluster worker (paper §3: prefill/decode disaggregation).

MegaScale-Infer decouples prefill from decoding so each phase gets its
own parallelism and hardware; the decode cluster's ping-pong pipeline is
sized for memory-bound single-token work and must never stall on a
compute-bound prompt pass.  This module is the prefill side of that
split:

  * ``PrefillWorker`` owns a *prefill device group* (its own mesh,
    disjoint from the decode cluster's attention/expert groups when
    enough devices exist) with a replicated copy of the parameters.
  * The engine feeds it waiting requests (``submit``), the worker runs
    **chunked, batched prefill** (``pump``): consecutive same-length
    prompts are batched into one ``models.prefill`` call, bounded by a
    ``chunk_tokens`` budget so one giant prompt batch cannot monopolise
    the prefill cluster (chunked-prefill-style TTFT isolation).
  * Each completed request is emitted onto a **transfer queue** as a
    ``PrefillResult`` handle — ``(first_token, request_kv)`` plus the
    last-position logits — in strict submission (FIFO) order.  The KV
    stays on the prefill cluster until the decode engine admits the
    request and ``serving.kvcache.migrate_kv`` reshards the rows onto
    the decode placement (the paper's KV-transfer hop).

Because prefill results depend only on the prompt, the prefill cluster
may run arbitrarily far ahead of decode-slot availability without
changing any generated token: admission into KV slots — not prefill
timing — determines decode batch composition, and under greedy sampling
the emitted tokens are identical to the inline-prefill engine.

Batching caveat: modality stubs (``models.stubs.extra_inputs``) generate
batch-shaped randoms, so archs that need them (vlm/audio) are prefilled
one request at a time to stay bit-identical with the inline path.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.config import ModelConfig
from repro.models import decode_step, prefill as model_prefill
from repro.models.stubs import extra_inputs
from repro.serving.kvcache import extract_row
from repro.serving.pages import row_to_page_chunks


@functools.lru_cache(maxsize=None)
def _suffix_scan(cfg: ModelConfig):
    """One jitted scan of ``decode_step`` over a token suffix.  Cached
    per (hashable, frozen) config; XLA caches per suffix length."""
    def run(params, toks, pos, row_cache):
        def body(cache, tp):
            tok, p = tp
            logits, cache = decode_step(params, cfg, tok[None], cache,
                                        p[None])
            return cache, logits[0]
        row_cache, logits = jax.lax.scan(body, row_cache, (toks, pos))
        return logits[-1][None], row_cache
    return jax.jit(run)


def suffix_prefill(params, cfg: ModelConfig, prompt: Sequence[int],
                   row_cache: dict, start: int):
    """Prefill only ``prompt[start:]`` on top of a cache row that
    already holds the first ``start`` tokens' KV (a radix prefix hit):
    the shared prefix is **not recomputed** — decode starts at the fork
    point.  The suffix runs as a single jitted ``decode_step`` scan on
    the B=1 row (one dispatch for the whole suffix; per-token cost is
    decode-shaped rather than prefill-shaped, and the win is skipping
    the prefix entirely — which dominates for the shared-system-prompt
    + short-suffix workload this path exists for).  Returns
    ``(last_logits (1, V), row_cache)``.
    """
    toks = jnp.asarray(list(prompt[start:]), jnp.int32)
    pos = jnp.arange(start, len(prompt), dtype=jnp.int32)
    return _suffix_scan(cfg)(params, toks, pos, row_cache)


@dataclass
class PrefillResult:
    """A completed prefill: the transfer-queue handle the engine admits.

    ``kv`` (a per-request cache pytree, batch dim 1) still lives on the
    prefill cluster; ``migrate_kv`` moves it onto the decode placement
    at admission time.  ``first_token`` is the greedy token as a 0-d
    array — kept lazy so emitting a handle never blocks the host on the
    prefill computation; the engine samples from ``last_logits`` with
    its own PRNG stream at admission instead.

    Paged layout: ``kv`` is None and ``page_chunks`` carries the
    non-shared KV as per-page chunks (``pages.row_to_page_chunks``) for
    ``kvcache.migrate_pages``; ``shared_pages`` / ``n_shared_tokens``
    name the radix-hit prefix pages (already pinned in the pool) that
    the engine links into the block table without any transfer."""
    request: object                   # serving.engine.Request
    last_logits: jax.Array            # (1, V) last-position logits
    first_token: jax.Array            # 0-d int32 (greedy argmax), lazy
    kv: Optional[dict]
    n_prompt_tokens: int
    t_prefill_s: float                # this request's share of batch time
    page_chunks: Optional[list] = None    # [(logical_page, chunk), ...]
    shared_pages: tuple = ()              # prefix-cache pages, pinned
    n_shared_tokens: int = 0


class PrefillWorker:
    """Runs batched prefill on its own device group, emits a FIFO
    transfer queue of ``PrefillResult`` handles."""

    def __init__(self, cfg: ModelConfig, params: dict,
                 devices: Optional[Sequence] = None, *, max_seq: int = 256,
                 chunk_tokens: int = 512,
                 prefill_fn: Optional[Callable] = None,
                 page_size: int = 0, page_pool=None, prefix_cache=None):
        """``devices``: the prefill cluster (default: first local device).
        ``chunk_tokens``: token budget per prefill batch — consecutive
        same-length prompts are batched while batch*plen stays within it
        (a single longer prompt always runs alone).  ``prefill_fn`` lets
        tests / alternative backends replace ``models.prefill``; it must
        match its ``(params, cfg, tokens, max_seq, **extras)`` signature.

        ``page_size`` > 0 switches the transfer queue to the paged KV
        layout: results carry per-page chunks instead of whole rows.
        With a ``prefix_cache`` (a ``serving.prefix_cache.PrefixCache``
        over the decode engine's ``page_pool``) a radix hit skips
        recomputing the shared prefix — the worker gathers the cached
        prefix pages and runs ``suffix_prefill`` from the fork point
        (hit requests run as single-request batches; miss batching is
        unchanged).  The engine wires its own pool/prefix in when the
        launcher didn't."""
        self.cfg = cfg
        self.max_seq = max_seq
        self.chunk_tokens = max(1, chunk_tokens)
        devs = list(devices) if devices else [jax.devices()[0]]
        self.mesh = Mesh(np.array(devs), ("prefill",))
        self.params = jax.device_put(params, NamedSharding(self.mesh, P()))
        self._prefill = prefill_fn or model_prefill
        self._needs_extras = bool(extra_inputs(cfg, 1))
        self.page_size = page_size
        self.page_pool = page_pool
        self.prefix_cache = prefix_cache
        self._hits: dict = {}               # rid -> (n_tokens, pages), pinned
        self.pending: deque = deque()       # submitted, not yet prefilled
        self.ready: deque = deque()         # the transfer queue (FIFO)
        self.n_prefills = 0
        self.n_batches = 0
        self.n_tokens = 0
        self.obs = obs.Recorder()       # prefill.batch spans

    # ------------------------------------------------------------- frontend
    def submit(self, request) -> None:
        self.pending.append(request)

    @property
    def ready_count(self) -> int:
        return len(self.ready)

    @property
    def pending_count(self) -> int:
        return len(self.pending)

    def pop(self) -> Optional[PrefillResult]:
        """Next completed prefill in submission order, or None."""
        return self.ready.popleft() if self.ready else None

    # ------------------------------------------------------------- prefill
    def _lookup(self, req):
        """One prefix-cache lookup per request (memoized — lookups pin
        the matched pages, so repeating one would double-pin)."""
        if req.rid not in self._hits:
            self._hits[req.rid] = self.prefix_cache.lookup(req.prompt)
        return self._hits[req.rid]

    def _next_batch(self) -> list:
        """Pop the next chunk: consecutive same-length prompts within the
        ``chunk_tokens`` budget (FIFO order is preserved by construction).
        Prefix-cache hits run alone (the suffix path is B=1); a hit
        further down the queue just ends the current batch early.
        """
        batch = [self.pending.popleft()]
        if self.prefix_cache is not None and self._lookup(batch[0])[0]:
            return batch
        plen = len(batch[0].prompt)
        if self._needs_extras:
            return batch
        while (self.pending and len(self.pending[0].prompt) == plen
               and (len(batch) + 1) * plen <= self.chunk_tokens):
            if self.prefix_cache is not None \
                    and self._lookup(self.pending[0])[0]:
                break
            batch.append(self.pending.popleft())
        return batch

    def _paged_fields(self, req, row_cache, h: int, pages) -> dict:
        """PrefillResult extras for the paged transfer queue: the
        non-shared slots ``[h, plen)`` as per-page chunks."""
        return {
            "kv": None,
            "page_chunks": row_to_page_chunks(
                row_cache, h, len(req.prompt), self.page_size),
            "shared_pages": tuple(pages),
            "n_shared_tokens": h,
        }

    def _run_suffix(self, req) -> None:
        """Radix-hit path: gather the cached prefix pages and compute
        only the suffix — the shared prefix is never re-run."""
        h, pages = self._hits.pop(req.rid)
        with self.obs.span("prefill.batch", size=1,
                           prompt_len=len(req.prompt)) as sp:
            row = self.page_pool.gather_row(pages)
            row = jax.device_put(row, NamedSharding(self.mesh, P()))
            last_logits, row = suffix_prefill(self.params, self.cfg,
                                              req.prompt, row, h)
            greedy = jnp.argmax(last_logits, -1)
        self.n_batches += 1
        self.ready.append(PrefillResult(
            request=req, last_logits=last_logits,
            first_token=greedy[0], n_prompt_tokens=len(req.prompt),
            t_prefill_s=sp.seconds,
            **self._paged_fields(req, row, h, pages)))
        self.n_prefills += 1
        self.n_tokens += len(req.prompt) - h

    def _run_batch(self, batch: list) -> None:
        if (self.prefix_cache is not None and len(batch) == 1
                and self._hits.get(batch[0].rid, (0,))[0]):
            self._run_suffix(batch[0])
            return
        with self.obs.span("prefill.batch", size=len(batch),
                           prompt_len=len(batch[0].prompt)) as sp:
            toks = jnp.asarray([r.prompt for r in batch], jnp.int32)
            extras = extra_inputs(self.cfg, len(batch))
            # pin capacity_mode to what the inline engine's per-request
            # (B=1) prefill would resolve "auto" to — batching must not
            # flip a request from drop-free "full" into bounded "eval"
            # capacity (models.prefill's auto threshold is B*T <= 2048),
            # or parity with the inline path breaks for large
            # chunk_tokens
            capacity = "full" if toks.shape[1] <= 2048 else "eval"
            last_logits, cache = self._prefill(self.params, self.cfg, toks,
                                               self.max_seq,
                                               capacity_mode=capacity,
                                               **extras)
            greedy = jnp.argmax(last_logits, -1)
        dt = sp.seconds
        self.n_batches += 1
        for i, req in enumerate(batch):
            row = extract_row(cache, i)
            self._hits.pop(req.rid, None)   # a (0, []) memoized miss
            extra = (self._paged_fields(req, row, 0, ())
                     if self.page_size else {"kv": row})
            self.ready.append(PrefillResult(
                request=req, last_logits=last_logits[i:i + 1],
                first_token=greedy[i],
                n_prompt_tokens=len(req.prompt),
                t_prefill_s=dt / len(batch), **extra))
            self.n_prefills += 1
            self.n_tokens += len(req.prompt)

    def pump(self, max_batches: Optional[int] = None) -> int:
        """Run up to ``max_batches`` prefill batches (default: drain the
        pending queue).  Returns the number of batches executed."""
        done = 0
        while self.pending and (max_batches is None or done < max_batches):
            self._run_batch(self._next_batch())
            done += 1
        return done

    # -------------------------------------------------------------- metrics
    def stats(self) -> dict:
        return {"prefill_s": self.obs.seconds("prefill.batch"),
                "prefills": self.n_prefills,
                "prefill_batches": self.n_batches,
                "prefill_tokens": self.n_tokens,
                "prefill_devices": len(self.mesh.devices.flat)}
