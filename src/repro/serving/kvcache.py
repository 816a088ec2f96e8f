"""Batch-slot KV-cache management for continuous batching.

This module manages the request->storage mapping so requests of
different lengths can join/leave the running batch between decode
iterations (Orca-style iteration-level scheduling, which both baselines
in the paper employ and MegaScale-Infer inherits).  Two KV layouts sit
behind it (``ServingConfig.kv_layout``):

  * **contiguous** (default): the model-level cache (models.init_cache)
    is a fixed (B_max, W) ring buffer per layer; a request owns one
    whole row for its lifetime (``SlotAllocator`` /
    ``MicrobatchSlotAllocator``), and the prefill->decode hop moves
    full rows (``migrate_kv``).
  * **paged**: rows are virtual — a request holds a block table of
    fixed-size refcounted pages in a ``serving.pages.PagePool``, shared
    prefixes are deduplicated by ``serving.prefix_cache.PrefixCache``,
    and the prefill->decode hop moves only the non-shared pages
    (``migrate_pages``).

Batch-row slots are still allocated in both layouts (a live request
needs a position in the decode batch either way); only the KV storage
behind the row differs.
"""
from __future__ import annotations

import functools
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax

from repro.config import ModelConfig
from repro.core import transport as transport_lib
from repro.core.pingpong import even_partition


def _unstacked(cache) -> List[str]:
    """The cache's groups of unstacked layers: the remainder, and the
    leading layers where the model has them."""
    return [k for k in ("lead", "remainder") if k in cache]


def insert_rows(global_cache, request_cache, row):
    """Write a single-request cache (batch dim 1) into row ``row``.

    Leaves shaped (n_blocks, 1, ...) go into (n_blocks, B, ...); lead and
    remainder leaves shaped (1, ...) into (B, ...).  Each write is a
    ``dynamic_update_slice``, so ``row`` may be a traced scalar.
    """

    def into(axis: int):
        def put(full, part):
            if part.ndim != full.ndim:
                raise ValueError((full.shape, part.shape))
            return lax.dynamic_update_slice_in_dim(
                full, part.astype(full.dtype), row, axis)
        return put

    out = {"blocks": jax.tree.map(into(1), global_cache["blocks"],
                                  request_cache["blocks"])}
    for key in _unstacked(global_cache):
        out[key] = jax.tree.map(into(0), global_cache[key],
                                request_cache[key])
    return out


@functools.lru_cache(maxsize=None)
def _row_writer(tree, shardings: tuple):
    return jax.jit(insert_rows, donate_argnums=0,
                   out_shardings=jax.tree.unflatten(tree, shardings))


def write_row(global_cache, request_cache, row: int):
    """``insert_rows`` as one compiled program with ``global_cache``
    donated: the row is written in place, with no second copy of the
    cache, and ``global_cache`` must not be used after the call.  The
    row index is traced, so every slot shares the program; each leaf
    comes back with the sharding it came in with (a leaf not committed
    to a device stays uncommitted)."""
    leaves, tree = jax.tree.flatten(global_cache)
    shardings = tuple(a.sharding if a.committed else None for a in leaves)
    return _row_writer(tree, shardings)(global_cache, request_cache,
                                        np.int32(row))


def extract_row(global_cache, row: int):
    """Slice one request's cache out of a batched cache (inverse of
    ``insert_rows``): blocks leaves (n_blocks, B, ...) -> (n_blocks, 1, ...),
    lead and remainder leaves (B, ...) -> (1, ...)."""
    out = {"blocks": tuple(jax.tree.map(lambda a: a[:, row:row + 1], e)
                           for e in global_cache["blocks"])}
    for key in _unstacked(global_cache):
        out[key] = tuple(jax.tree.map(lambda a: a[row:row + 1], e)
                         for e in global_cache[key])
    return out


def migrate_kv(decode_cache, request_cache, row: int, *, sharding=None,
               sync: bool = False, transport=None):
    """The paper's prefill->decode KV-transfer hop: reshard one request's
    prefill-side cache (batch dim 1) onto the decode placement and write
    it into KV row ``row`` of the decode cache.

    ``sharding``: target placement of the migrated rows — e.g. the
    decode runtime's attention-mesh sharding (the attention group owns
    the KV cache).  Defaults to wherever the decode cache already lives.
    ``sync=True`` blocks until the transfer lands before the insert
    (sync transfer mode); by default the copy is issued asynchronously
    and overlaps whatever decode work is still in flight (JAX async
    dispatch — the analogue of the paper's layer-wise KV streaming).

    The hop goes through ``transport`` (a ``core.transport.Transport``),
    which accounts per-hop bytes/latency under the "kv" kind; the
    process-wide default in-process backend is used when none is given.
    """
    if transport is None:
        transport = transport_lib.default_transport()
    if sharding is None:
        sharding = jax.tree.leaves(decode_cache)[0].sharding
    moved = transport.migrate_kv(request_cache, sharding, sync=sync).data
    return insert_rows(decode_cache, moved, row)


def migrate_pages(pool, chunks: Sequence[Tuple[int, dict]],
                  pages: Sequence[int], *, sharding=None,
                  sync: bool = False, transport=None):
    """Page-granular prefill->decode KV transfer: move per-page chunks
    (as produced by ``pages.row_to_page_chunks`` on the prefill side)
    onto the decode placement and install them into physical ``pages``
    of the decode-side ``PagePool``.

    This is the paged analogue of ``migrate_kv`` — and the reason the
    paged layout makes the KV hop cheap: with a prefix-cache hit only
    the request's *non-shared* pages appear in ``chunks``, so shared
    system-prompt KV never crosses the prefill->decode boundary at all.
    Each page is priced as its own ``kind="kv"`` transport hop, giving
    the ledger per-page bytes accounting (``sync=True`` blocks per page;
    the default issues all copies asynchronously and lets them overlap
    decode compute).
    """
    if transport is None:
        transport = transport_lib.default_transport()
    if sharding is None:
        sharding = jax.tree.leaves(pool.store)[0].sharding
    if len(chunks) != len(pages):
        raise ValueError(f"{len(pages)} pages for {len(chunks)} chunks")
    for (_, chunk), page in zip(chunks, pages):
        moved = transport.migrate_pages(chunk, sharding, sync=sync).data
        pool.write_chunk(page, moved)
    return pool


def reset_row(global_cache, cfg: ModelConfig, row: int, max_seq: int):
    """Invalidate a row (request finished): mark kv positions empty and
    zero recurrent state, so a recycled KV slot can never expose the
    previous request's cache (the engine calls this on slot release)."""

    def rst_entry(entry):
        out = dict(entry)
        if "pos" in out:
            # stacked: (n_blocks, B, W) or flat (B, W)
            p = out["pos"]
            out["pos"] = (p.at[:, row].set(-1) if p.ndim == 3
                          else p.at[row].set(-1))
        if "h" in out:
            h = out["h"]
            out["h"] = (h.at[:, row].set(0) if h.ndim == 3
                        else h.at[row].set(0))
        if "ssm" in out:
            s = out["ssm"]
            idx = (slice(None), row) if s.ndim == 5 else (row,)
            out["ssm"] = s.at[idx].set(0)
        return out

    return {key: tuple(rst_entry(e) for e in global_cache[key])
            for key in ["blocks"] + _unstacked(global_cache)}


class SlotAllocator:
    """FIFO batch-row allocator.

    Invariant (checked, not assumed): a slot is held by at most one
    request at a time — same guarantee ``MicrobatchSlotAllocator``
    enforces.  The free list is a deque so alloc is O(1), not the
    O(n) ``list.pop(0)`` it used to be.
    """

    def __init__(self, n_slots: int):
        self.free: Deque[int] = deque(range(n_slots))
        self.used: Dict[int, int] = {}  # request id -> slot
        self._held = set()              # slots currently assigned

    def alloc(self, rid: int) -> Optional[int]:
        if rid in self.used:
            raise ValueError(f"request {rid} already holds slot "
                             f"{self.used[rid]}")
        if not self.free:
            return None
        slot = self.free.popleft()
        if slot in self._held:
            raise RuntimeError(f"KV slot {slot} double-assigned "
                               f"(rid={rid}, holder={self.used})")
        self._held.add(slot)
        self.used[rid] = slot
        return slot

    def release(self, rid: int) -> int:
        slot = self.used.pop(rid)
        self._held.discard(slot)
        self.free.append(slot)
        return slot


def mb_slot_ranges(n_slots: int, m: int) -> List[slice]:
    """Partition ``n_slots`` KV rows into <= m contiguous micro-batch
    groups of near-even size (``pingpong.even_partition``).

    Contiguity is what makes the ping-pong engine's per-micro-batch cache
    views plain array slices — no gather when shuttling a micro-batch to
    the expert group."""
    return even_partition(n_slots, m)


class MicrobatchSlotAllocator:
    """Slot allocator aware of micro-batch groups (ping-pong serving).

    Each KV slot belongs to exactly one micro-batch group (a contiguous
    row range from ``mb_slot_ranges``).  Requests are admitted into a
    specific group — or, by default, the group with the most free slots,
    which keeps micro-batch loads balanced as requests of different
    lengths churn (Orca-style recycling at micro-batch granularity).

    Invariant (checked, not assumed): a slot is held by at most one
    request at a time, and is only ever returned to its own group.
    """

    def __init__(self, n_slots: int, groups: List[slice]):
        if groups[0].start != 0 or groups[-1].stop != n_slots or any(
                a.stop != b.start for a, b in zip(groups, groups[1:])):
            raise ValueError(f"groups {groups} must tile [0, {n_slots})")
        self.groups = list(groups)
        self.free_by_group: List[Deque[int]] = [
            deque(range(s.start, s.stop)) for s in groups]
        self.used: Dict[int, int] = {}      # request id -> slot
        self._held = set()                  # slots currently assigned
        # precomputed slot -> group index so release is O(1), not a
        # linear scan over the group ranges
        self._slot_group: List[int] = [0] * n_slots
        for gi, s in enumerate(groups):
            for slot in range(s.start, s.stop):
                self._slot_group[slot] = gi

    @property
    def free(self) -> List[int]:
        return [s for g in self.free_by_group for s in g]

    def group_of(self, slot: int) -> int:
        if not 0 <= slot < len(self._slot_group):
            raise ValueError(f"slot {slot} outside all groups")
        return self._slot_group[slot]

    def alloc(self, rid: int, group: Optional[int] = None) -> Optional[int]:
        if rid in self.used:
            raise ValueError(f"request {rid} already holds slot "
                             f"{self.used[rid]}")
        if group is None:
            candidates = [gi for gi, f in enumerate(self.free_by_group) if f]
            if not candidates:
                return None
            group = max(candidates, key=lambda gi: len(self.free_by_group[gi]))
        if not self.free_by_group[group]:
            return None
        slot = self.free_by_group[group].popleft()
        if slot in self._held:
            raise RuntimeError(f"KV slot {slot} double-assigned "
                               f"(rid={rid}, holder={self.used})")
        self._held.add(slot)
        self.used[rid] = slot
        return slot

    def release(self, rid: int) -> int:
        slot = self.used.pop(rid)
        self._held.discard(slot)
        self.free_by_group[self.group_of(slot)].append(slot)
        return slot
