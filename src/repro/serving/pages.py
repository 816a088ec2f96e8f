"""Paged KV-cache subsystem: a fixed-size page pool with refcounted
pages and per-request block tables.

The contiguous layout (``models.init_cache``) gives every KV slot a
whole ``(W,)`` ring-buffer row for the life of the request.  The paged
layout instead carves the KV storage into fixed-size **pages** of
``page_size`` token slots, shared across every layer: one physical page
id selects the same page index in every layer's K/V/pos store, so a
single per-request **block table** (logical page -> physical page)
describes the whole cache.  This is the vLLM-style memory model, and it
is what the three scale directions in the ROADMAP sit behind:

  * requests only hold pages they have actually written (long-context
    admission no longer reserves ``max_seq`` rows up front — admission
    reserves worst-case pages explicitly, so it is OOM-safe by
    accounting, not by luck);
  * the prefill->decode KV hop can move *pages* instead of whole rows
    (``kvcache.migrate_pages``), and with a prefix hit only the
    non-shared pages cross the wire;
  * pages are refcounted, so several requests (and the radix prefix
    cache, ``serving.prefix_cache``) can share one physical page, with
    copy-on-write forking when a writer would touch a shared page.

Correspondence with the contiguous layout is exact: ``gather`` of a
block table reproduces the dense ``(B, W)`` cache pytree bit-for-bit
(unwritten / unmapped slots carry ``pos = -1`` exactly like a freshly
reset row), which is how the serving engine keeps paged decode
token-identical to the contiguous path — the decode computation itself
is unchanged, only the storage behind it is paged.

Layout of the pool's device storage (mirrors ``init_cache``):

  contiguous leaf                      paged leaf
  k/v  (n_blocks, B, W, Hkv, hd)  ->   (n_blocks, P, ps, Hkv, hd)
  pos  (n_blocks, B, W)           ->   (n_blocks, P, ps)

with ``P = n_pages`` physical pages of ``ps = page_size`` slots.  Only
pure-KV cache entries (keys exactly {k, v, pos}, window == max_seq) can
be paged; archs with recurrent / cross-attention state keep the
contiguous layout (``paged_supported`` reports why).
"""
from __future__ import annotations

from collections import deque
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import MLA_KINDS, ModelConfig
from repro.models import init_cache

KV_KEYS = frozenset(("k", "v", "pos"))


class PageError(RuntimeError):
    """Page-pool invariant violation or out-of-pages condition."""


def paged_supported(cfg: ModelConfig, max_seq: int,
                    page_size: int) -> Tuple[bool, str]:
    """Whether ``cfg``'s cache can use the paged layout, and why not.

    Requirements: every cache entry is a pure KV ring buffer (keys
    exactly {k, v, pos}) whose window spans the full ``max_seq`` (a
    "local" layer with a smaller window wraps at a different period
    than the shared block table), and ``max_seq`` divides into whole
    pages."""
    if page_size <= 0:
        return False, f"page_size must be positive, got {page_size}"
    if max_seq % page_size:
        return False, (f"max_seq={max_seq} is not a whole number of "
                       f"pages of {page_size}")
    for kind in cfg.layer_kinds:
        if kind in MLA_KINDS:
            return False, (f"layer kind {kind!r}: multi-head latent "
                           f"attention's latent cache is not yet served "
                           f"by the paged layout")
        if kind not in ("attn", "local"):
            return False, (f"layer kind {kind!r} carries non-KV cache "
                           f"state (paged layout pages only k/v/pos)")
        if kind == "local" and min(cfg.window, max_seq) != max_seq:
            return False, (f"'local' window {cfg.window} < max_seq "
                           f"{max_seq}: ring period differs from the "
                           f"block table's")
    return True, ""


def n_pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` consecutive slots."""
    return -(-max(0, n_tokens) // page_size)


# --------------------------------------------------------------------------
# pure helpers over dense-row pytrees (shared with the prefill worker)
# --------------------------------------------------------------------------


def _is_blocks_leaf(a) -> bool:
    # blocks leaves carry the stacked layer dim in front: (n_blocks, B, ...)
    return a.ndim >= 3


def row_to_page_chunks(row_cache: dict, start_slot: int, end_slot: int,
                       page_size: int) -> List[Tuple[int, dict]]:
    """Split one request's dense cache row (batch dim 1, as produced by
    ``kvcache.extract_row`` or a B=1 prefill) into per-page chunks.

    Returns ``[(logical_page_index, chunk_pytree), ...]`` covering slots
    ``[start_slot, end_slot)``; ``start_slot`` must be page-aligned (the
    non-shared tail always starts at a page boundary).  Chunk leaves
    drop the batch dim: blocks k/v ``(n_blocks, ps, Hkv, hd)``, pos
    ``(n_blocks, ps)`` — exactly one pool page per layer store.
    """
    if start_slot % page_size:
        raise PageError(f"chunk start {start_slot} not page-aligned "
                        f"(page_size={page_size})")
    chunks = []
    for lp in range(start_slot // page_size,
                    n_pages_for(end_slot, page_size)):
        s0 = lp * page_size

        def cut(a):
            if _is_blocks_leaf(a):          # (n_blocks, 1, W, ...)
                return a[:, 0, s0:s0 + page_size]
            return a[0, s0:s0 + page_size]  # (1, W, ...) remainder

        chunks.append((lp, {
            "blocks": tuple(jax.tree.map(cut, e) for e in row_cache["blocks"]),
            "remainder": tuple(jax.tree.map(cut, e)
                               for e in row_cache["remainder"]),
        }))
    return chunks


def _map_entries(fn, cache: dict) -> dict:
    return {"blocks": tuple(jax.tree.map(fn, e) for e in cache["blocks"]),
            "remainder": tuple(jax.tree.map(fn, e)
                               for e in cache["remainder"])}


class PagePool:
    """Fixed-size pool of refcounted KV pages shared by every layer.

    Host-side state (free list, refcounts, reservations) is plain
    Python — allocation decisions never touch the device.  Device-side
    state is one paged store per cache entry (see module docstring).

    Invariants (checked, not assumed):
      * a page is either on the free list or has refcount >= 1;
      * ``free + in_use == n_pages`` at all times;
      * reservations never exceed the free count, so an admitted
        request can always grow to its reserved worst case (OOM-safe
        admission by accounting).
    """

    def __init__(self, cfg: ModelConfig, *, n_pages: int, page_size: int,
                 max_seq: int, dtype=jnp.float32):
        ok, why = paged_supported(cfg, max_seq, page_size)
        if not ok:
            raise PageError(f"paged KV layout unsupported for "
                            f"{cfg.name}: {why}")
        if n_pages <= 0:
            raise PageError(f"n_pages must be positive, got {n_pages}")
        self.cfg = cfg
        self.page_size = page_size
        self.max_seq = max_seq
        self.n_pages = n_pages
        self.n_logical = max_seq // page_size
        self.dtype = dtype
        # device storage: reuse init_cache's per-entry shapes with the
        # (B, W) row grid replaced by the (P, ps) page grid
        proto = init_cache(cfg, 1, max_seq, dtype)

        def paged(a):
            if _is_blocks_leaf(a):  # (n_blocks, 1, W, ...) -> (n_blocks, P, ps, ...)
                shape = (a.shape[0], n_pages, page_size) + a.shape[3:]
            else:                   # (1, W, ...) -> (P, ps, ...)
                shape = (n_pages, page_size) + a.shape[2:]
            if a.dtype == jnp.int32:  # pos leaves start invalid
                return jnp.full(shape, -1, jnp.int32)
            return jnp.zeros(shape, a.dtype)

        self.store = _map_entries(paged, proto)
        # host bookkeeping
        self.free: deque = deque(range(n_pages))
        self.refcount = np.zeros((n_pages,), np.int32)
        self.reserved = 0
        # stats
        self.high_water = 0
        self.n_allocs = 0
        self.n_forks = 0
        self.n_released = 0

    # ------------------------------------------------------------- accounting
    @property
    def used(self) -> int:
        return self.n_pages - len(self.free)

    @property
    def available(self) -> int:
        """Pages allocatable without eating into reservations."""
        return len(self.free) - self.reserved

    def reserve(self, n: int) -> bool:
        """Set aside ``n`` pages for a future holder.  Admission-time
        worst-case reservation is what makes paged admission OOM-safe:
        a request admitted with its full page budget reserved can never
        fail a mid-decode allocation."""
        if n < 0:
            raise PageError(f"reserve({n})")
        if n > self.available:
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int):
        if n < 0 or n > self.reserved:
            raise PageError(f"unreserve({n}) with {self.reserved} reserved")
        self.reserved -= n

    # ------------------------------------------------------------ page lifecycle
    def alloc(self, *, from_reserve: bool = False, _reset: bool = True) -> int:
        """Allocate one page (refcount 1).  ``from_reserve`` consumes a
        page previously set aside with ``reserve``.  The page's ``pos``
        slots are reset to -1 so a recycled page can never expose stale
        validity from its previous holder (k/v bytes may be stale — they
        are unreachable behind ``pos = -1``)."""
        if from_reserve:
            if self.reserved <= 0:
                raise PageError("alloc(from_reserve=True) with no "
                                "reservation outstanding")
            self.reserved -= 1
        elif self.available <= 0:
            raise PageError(f"out of pages ({self.n_pages} total, "
                            f"{self.reserved} reserved)")
        if not self.free:
            raise PageError("free list empty (reservation accounting bug)")
        page = self.free.popleft()
        if self.refcount[page]:
            raise PageError(f"page {page} on free list with refcount "
                            f"{self.refcount[page]}")
        self.refcount[page] = 1
        self.n_allocs += 1
        self.high_water = max(self.high_water, self.used)
        if _reset:
            def rst(a):
                if a.dtype != jnp.int32:
                    return a
                if _is_blocks_leaf(a):
                    return a.at[:, page].set(-1)
                return a.at[page].set(-1)
            self.store = _map_entries(rst, self.store)
        return page

    def retain(self, page: int):
        if self.refcount[page] <= 0:
            raise PageError(f"retain of free page {page}")
        self.refcount[page] += 1

    def release(self, page: int):
        if self.refcount[page] <= 0:
            raise PageError(f"release of free page {page}")
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self.free.append(page)
            self.n_released += 1

    def is_shared(self, page: int) -> bool:
        return self.refcount[page] > 1

    def fork(self, page: int, *, from_reserve: bool = False) -> int:
        """Copy-on-write fork: allocate a fresh page, copy ``page``'s
        contents into it, and drop one reference to the original.
        Callers that are about to write into a shared page swap the
        returned id into their block table; every other holder keeps
        the pristine original."""
        if self.refcount[page] <= 0:
            raise PageError(f"fork of free page {page}")
        new = self.alloc(from_reserve=from_reserve, _reset=False)

        def cp(a):
            if _is_blocks_leaf(a):
                return a.at[:, new].set(a[:, page])
            return a.at[new].set(a[page])

        self.store = _map_entries(cp, self.store)
        self.release(page)
        self.n_forks += 1
        return new

    # ----------------------------------------------------------------- device IO
    def write_row_span(self, pages: Sequence[int], row_cache: dict,
                       start_slot: int, end_slot: int):
        """Write slots ``[start_slot, end_slot)`` of a dense cache row
        (batch dim 1) into ``pages`` (one physical page per covered
        logical page, in order).  ``start_slot`` must be page-aligned;
        the last page is written in full (trailing slots carry the
        row's ``pos = -1``, i.e. stay invalid)."""
        chunks = row_to_page_chunks(row_cache, start_slot, end_slot,
                                    self.page_size)
        if len(chunks) != len(pages):
            raise PageError(f"{len(pages)} pages for {len(chunks)} chunks")
        for (_, chunk), page in zip(chunks, pages):
            self.write_chunk(page, chunk)

    def write_chunk(self, page: int, chunk: dict):
        """Install one page-shaped chunk (as produced by
        ``row_to_page_chunks`` / moved by ``kvcache.migrate_pages``)
        into physical ``page``."""
        if self.refcount[page] <= 0:
            raise PageError(f"write to free page {page}")

        def ins(full, part):
            if _is_blocks_leaf(full):
                return full.at[:, page].set(part.astype(full.dtype))
            return full.at[page].set(part.astype(full.dtype))

        self.store = {
            "blocks": tuple(
                jax.tree.map(ins, f, p) for f, p in
                zip(self.store["blocks"], chunk["blocks"])),
            "remainder": tuple(
                jax.tree.map(ins, f, p) for f, p in
                zip(self.store["remainder"], chunk["remainder"])),
        }

    def write_tokens(self, dense_cache: dict, rows: np.ndarray,
                     slots: np.ndarray, pages: np.ndarray,
                     offsets: np.ndarray):
        """Scatter freshly decoded per-token KV back into the pool.

        ``dense_cache`` is the decode step's output (the gathered view
        plus this iteration's writes); for each i, dense row
        ``rows[i]`` slot ``slots[i]`` lands in physical page
        ``pages[i]`` offset ``offsets[i]``.  One vectorized scatter per
        leaf — the per-step paged write-back cost is O(B), not O(B*W).
        """
        if len(rows) == 0:
            return
        rows = jnp.asarray(rows, jnp.int32)
        slots = jnp.asarray(slots, jnp.int32)
        pages = jnp.asarray(pages, jnp.int32)
        offs = jnp.asarray(offsets, jnp.int32)

        def scatter(full, dense):
            if _is_blocks_leaf(full):   # (n_blocks, P, ps, ...) <- (n_blocks, B, W, ...)
                vals = dense[:, rows, slots]
                return full.at[:, pages, offs].set(vals.astype(full.dtype))
            vals = dense[rows, slots]
            return full.at[pages, offs].set(vals.astype(full.dtype))

        self.store = {
            "blocks": tuple(
                jax.tree.map(scatter, f, d) for f, d in
                zip(self.store["blocks"], dense_cache["blocks"])),
            "remainder": tuple(
                jax.tree.map(scatter, f, d) for f, d in
                zip(self.store["remainder"], dense_cache["remainder"])),
        }

    def gather(self, block_tables: np.ndarray) -> dict:
        """Materialize the dense ``(B, W)`` cache view for a batch of
        block tables (``(B, n_logical)`` int32, -1 = unmapped).

        This is the block-table-indexed gather path: unmapped logical
        pages read as empty (``pos = -1``), so the result is exactly
        what the contiguous layout's cache would hold — the decode
        computation downstream needs no layout awareness at all."""
        bt = jnp.asarray(block_tables, jnp.int32)
        if bt.ndim != 2 or bt.shape[1] != self.n_logical:
            raise PageError(f"block table shape {bt.shape} != "
                            f"(B, {self.n_logical})")
        B = bt.shape[0]
        W = self.max_seq
        btc = jnp.maximum(bt, 0)
        mapped = (bt >= 0)[:, :, None]  # (B, n_logical, 1) slot broadcast

        def g(a):
            if _is_blocks_leaf(a):      # (n_blocks, P, ps, ...)
                v = a[:, btc]           # (n_blocks, B, n_logical, ps, ...)
                if a.dtype == jnp.int32:
                    v = jnp.where(mapped[None], v, -1)
                return v.reshape((a.shape[0], B, W) + a.shape[3:])
            v = a[btc]                  # (B, n_logical, ps, ...)
            if a.dtype == jnp.int32:
                v = jnp.where(mapped, v, -1)
            return v.reshape((B, W) + a.shape[2:])

        return _map_entries(g, self.store)

    def gather_row(self, pages: Sequence[int]) -> dict:
        """Dense single-request row (batch dim 1) for a page chain —
        the inverse of ``write_row_span`` (logical pages beyond the
        chain read as empty)."""
        bt = np.full((1, self.n_logical), -1, np.int32)
        bt[0, :len(pages)] = pages
        return self.gather(bt)

    # -------------------------------------------------------------- metrics
    def stats(self) -> dict:
        return {
            "n_pages": self.n_pages,
            "page_size": self.page_size,
            "used": self.used,
            "free": len(self.free),
            "reserved": self.reserved,
            "high_water": self.high_water,
            "utilization": self.used / self.n_pages,
            "allocs": self.n_allocs,
            "forks": self.n_forks,
            "released": self.n_released,
        }
