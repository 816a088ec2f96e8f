"""Unified pluggable M2N transport layer (paper §4.2).

The paper's M2N library exists to move tokens between disaggregated
attention and FFN nodes with zero-copy, low-latency semantics.  Before
this module the repo did "transport" three different ways on one host:
a ``shard_map`` inside ``core.m2n`` for dispatch, ad-hoc ``device_put``
in ``serving.kvcache.migrate_kv`` for KV migration, and an inline
regather in ``core.disagg.apply_placement``.  Every hop now goes through
one ``Transport`` interface with per-hop bytes + latency accounting, and
the backend is pluggable:

  * ``InProcessTransport`` — today's single-process ``device_put`` /
    ``shard_map`` path, token-identical to the pre-transport code.
  * ``MultiControllerTransport`` — ``jax.distributed.initialize`` +
    multi-process global meshes (CPU collectives via gloo), bring-up
    ergonomics modeled on MPI launch scripts: explicit args, or env
    (``REPRO_COORDINATOR``/``REPRO_NUM_PROCESSES``/``REPRO_PROCESS_ID``,
    with OpenMPI/SLURM rank variables understood as fallbacks).
  * ``SimRdmaTransport`` — real in-process movement plus an alpha-beta
    RDMA/NCCL cost model per hop, so the fig10/fig11 M2N numbers come
    from a transport instance instead of hardcoded formulas.

Hop kinds map onto the three serving token-movement paths:

  ``tokens``      M2N dispatch / N2M return of token shards
  ``kv``          prefill->decode KV page/row migration
  ``weights``     expert-weight regathers (live placement, param upload)
  ``collective``  in-graph combine collectives (psum inside shard_map),
                  accounted analytically — the wire bytes are known in
                  closed form and the op itself executes inside jit.
"""
from __future__ import annotations

import abc
import math
import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np

HOP_KINDS = ("tokens", "kv", "weights", "collective")


def tree_nbytes(tree) -> int:
    """Total payload bytes of a pytree of arrays.  Hot path: called once
    per hop from inside the profiled dispatch/combine stages, so it must
    stay a few us — ``math.prod(shape)`` + the concrete dtype's itemsize
    (no ``canonicalize_dtype``, no ``.nbytes`` property, both ~5x
    slower per leaf)."""
    return sum(math.prod(a.shape) * np.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


@dataclass
class TransportHandle:
    """One completed (or in-flight) transport hop.

    ``data`` is the moved pytree (JAX async dispatch: the transfer may
    still be in flight unless the hop was issued ``sync``).  ``nbytes``
    is the wire-byte model for the hop: payload bytes times the fan-out
    (peers receiving a copy).  ``issue_s`` is host time spent issuing;
    ``sim_s`` is the simulated wire latency (0 for real backends)."""
    kind: str
    nbytes: int
    issue_s: float
    sim_s: float = 0.0
    fanout: int = 1
    data: Any = None

    def block(self):
        """Wait for the hop's data to land (sync semantics after the fact)."""
        jax.block_until_ready(self.data)
        return self


def _empty_stats() -> dict:
    return {k: {"hops": 0, "bytes": 0, "issue_s": 0.0, "sim_s": 0.0}
            for k in HOP_KINDS}


class Transport(abc.ABC):
    """Send/recv of token shards, KV rows, and weight regathers.

    Concrete backends implement ``send``; the convenience wrappers fix
    the hop kind for the three serving paths.  All hops are accounted
    per kind in ``stats()`` — the serving engine surfaces the snapshot
    in ``Engine.stats()["transport"]`` and ``serve_bench`` records it.
    """

    name = "abstract"

    def __init__(self):
        self._stats = _empty_stats()

    # ------------------------------------------------------------------ hops
    @abc.abstractmethod
    def send(self, tree, sharding, *, kind: str = "tokens",
             sync: bool = False, fanout: int = 1) -> TransportHandle:
        """Move ``tree`` onto ``sharding``; returns the accounting handle
        (``handle.data`` is the moved pytree).  ``sync`` blocks until the
        transfer lands; ``fanout`` is the number of peers receiving a
        copy (scales the hop's wire-byte model)."""

    def send_tokens(self, x, sharding, *, sync: bool = False,
                    fanout: int = 1) -> TransportHandle:
        """M2N dispatch / N2M return hop of token activations."""
        return self.send(x, sharding, kind="tokens", sync=sync, fanout=fanout)

    def migrate_kv(self, request_kv, sharding, *,
                   sync: bool = False) -> TransportHandle:
        """Prefill->decode KV hop: one request's cache rows."""
        return self.send(request_kv, sharding, kind="kv", sync=sync)

    def migrate_pages(self, page_chunk, sharding, *,
                      sync: bool = False) -> TransportHandle:
        """Page-granular prefill->decode KV hop: one fixed-size page's
        worth of cache across all layers (paged KV layout).  Same wire
        kind as ``migrate_kv`` — the ledger sees one "kv" hop *per
        page*, so bytes scale with pages actually moved, not with the
        request's reserved row."""
        return self.send(page_chunk, sharding, kind="kv", sync=sync)

    def regather_weights(self, tree, sharding, *,
                         fanout: int = 1) -> TransportHandle:
        """Expert-weight regather (live placement / param upload)."""
        return self.send(tree, sharding, kind="weights", fanout=fanout)

    def record_collective(self, nbytes: int, *, fanout: int = 1) -> TransportHandle:
        """Account an in-graph collective hop (e.g. the M2N combine psum
        inside ``shard_map``) whose wire bytes are known analytically.
        No data moves here — the collective executes inside jit; this is
        the accounting side-channel."""
        h = TransportHandle(kind="collective", nbytes=int(nbytes),
                            issue_s=0.0, fanout=fanout)
        h.sim_s = self._simulate(h)
        self._account(h)
        return h

    def gather(self, tree):
        """Host-readable view of (possibly process-global) arrays."""
        return jax.tree.map(np.asarray, tree)

    # ------------------------------------------------------------- accounting
    def _simulate(self, handle: TransportHandle) -> float:
        return 0.0

    def _account(self, handle: TransportHandle):
        s = self._stats[handle.kind]
        s["hops"] += 1
        s["bytes"] += handle.nbytes
        s["issue_s"] += handle.issue_s
        s["sim_s"] += handle.sim_s

    def stats(self) -> dict:
        """Per-kind cumulative hop counters plus the backend name."""
        out = {"backend": self.name}
        for k, s in self._stats.items():
            if s["hops"]:
                out[k] = dict(s)
        return out

    def reset_stats(self):
        self._stats = _empty_stats()


class InProcessTransport(Transport):
    """Single-process backend: ``jax.device_put`` resharding — the JAX
    analogue of a receiver-addressed RDMA write (no host staging), and
    exactly the path the repo used before the transport abstraction, so
    serving output is token-identical."""

    name = "inproc"

    def send(self, tree, sharding, *, kind: str = "tokens",
             sync: bool = False, fanout: int = 1) -> TransportHandle:
        t0 = time.perf_counter()
        moved = jax.device_put(tree, sharding)
        if sync:
            jax.block_until_ready(moved)
        h = TransportHandle(kind=kind, nbytes=tree_nbytes(tree) * max(1, fanout),
                            issue_s=time.perf_counter() - t0,
                            fanout=fanout, data=moved)
        h.sim_s = self._simulate(h)
        self._account(h)
        return h


# --------------------------------------------------------------- cost model
@dataclass(frozen=True)
class RdmaCostModel:
    """Alpha-beta network model for one-to-N transfers (paper §5 fig10/11).

    ``alpha_s`` is the per-op-batch setup cost (NCCL: group setup + GPU
    sync, batched ``group`` P2P ops at a time; M2N: one CQ poll), and
    ``per_op_s`` the per-peer issue cost (NCCL: proxy copy + launch +
    checks; M2N: one RDMA write-with-immediate).  ``jitter_p99_s`` is
    the per-batch tail jitter that makes NCCL's P99 blow up with N."""
    alpha_s: float
    per_op_s: float
    bw_Bps: float
    group: int = 1
    jitter_p99_s: float = 0.0
    tail_floor_s: float = 0.0

    def one_to_n(self, size_bytes: int, n: int) -> float:
        """Median latency of one sender writing ``size_bytes`` to each
        of ``n`` receivers."""
        batches = -(-n // self.group)
        return (batches * self.alpha_s + n * self.per_op_s
                + n * size_bytes / self.bw_Bps)

    def p99_one_to_n(self, size_bytes: int, n: int) -> float:
        batches = -(-n // self.group)
        return (self.one_to_n(size_bytes, n)
                + batches * self.jitter_p99_s + self.tail_floor_s)

    @classmethod
    def nccl_grouped_p2p(cls) -> "RdmaCostModel":
        """NCCL-like grouped peer-to-peer: per-op launch overhead times
        ceil(N/8) op batches, GPU-sync + proxy-copy alpha.  Constants
        from the paper's §5 measurements (200 Gbps NIC)."""
        return cls(alpha_s=40e-6, per_op_s=15e-6, bw_Bps=25e9, group=8,
                   jitter_p99_s=120e-6)

    @classmethod
    def m2n_rdma(cls) -> "RdmaCostModel":
        """The paper's M2N library: a single pre-registered RDMA write
        per peer, no staging, flat tail."""
        return cls(alpha_s=6e-6, per_op_s=1e-6, bw_Bps=25e9, group=10 ** 9,
                   jitter_p99_s=0.0, tail_floor_s=8e-6)


class SimRdmaTransport(InProcessTransport):
    """Simulated-RDMA backend: data still moves in-process (serving
    stays correct), but every hop also accrues latency from an
    ``RdmaCostModel`` — the per-hop numbers fig10/fig11 and the
    ``serve_bench`` transport entries report.  ``default_fanout`` is the
    peer count assumed for hops that don't specify one."""

    name = "simrdma"

    def __init__(self, model: Optional[RdmaCostModel] = None, *,
                 default_fanout: int = 1):
        super().__init__()
        self.model = model if model is not None else RdmaCostModel.m2n_rdma()
        self.default_fanout = max(1, default_fanout)

    def _simulate(self, handle: TransportHandle) -> float:
        n = max(1, handle.fanout if handle.fanout > 1 else self.default_fanout)
        return self.model.one_to_n(handle.nbytes // max(1, n), n)


# ------------------------------------------------------- multi-controller
def _distributed_initialized() -> bool:
    """Whether ``jax.distributed.initialize`` already ran — checked via
    the distributed client state, NOT ``jax.process_count()``: touching
    the backend before initialize would lock JAX into single-process
    mode ("must be called before any JAX computations")."""
    return jax.distributed.is_initialized()


def _env_int(*names: str) -> Optional[int]:
    for n in names:
        v = os.environ.get(n)
        if v is not None:
            return int(v)
    return None


@dataclass
class DistributedSpec:
    """Multi-process bring-up parameters (MPI-launch ergonomics): pass
    explicitly, or resolve from env — our own variables first, then the
    OpenMPI / SLURM rank variables the usual launchers export."""
    coordinator: str = "127.0.0.1:12357"
    num_processes: int = 1
    process_id: int = 0

    @classmethod
    def from_env(cls) -> "DistributedSpec":
        coord = os.environ.get("REPRO_COORDINATOR", "127.0.0.1:12357")
        nproc = _env_int("REPRO_NUM_PROCESSES", "OMPI_COMM_WORLD_SIZE",
                         "SLURM_NTASKS") or 1
        pid = _env_int("REPRO_PROCESS_ID", "OMPI_COMM_WORLD_RANK",
                       "SLURM_PROCID") or 0
        return cls(coordinator=coord, num_processes=nproc, process_id=pid)


class MultiControllerTransport(Transport):
    """Multi-process backend: ``jax.distributed.initialize`` + global
    meshes spanning every process's local devices.

    Within the addressable slice it behaves like ``InProcessTransport``;
    for shardings that span processes it follows the multihost
    convention — each process passes its *host-local* view (identical
    full arrays for replicated specs, the local slice for sharded ones)
    and receives the process-global array.  Cross-process wire traffic
    then happens inside jitted collectives (on CPU via the gloo
    collectives implementation, enabled at bring-up)."""

    name = "multi"

    def __init__(self, spec: Optional[DistributedSpec] = None, *,
                 cpu_collectives: str = "gloo", initialize: bool = True):
        super().__init__()
        self.spec = spec if spec is not None else DistributedSpec.from_env()
        if initialize and self.spec.num_processes > 1 \
                and not _distributed_initialized():
            # gloo makes multi-process computations work on the CPU
            # backend (the default errors with "Multiprocess computations
            # aren't implemented"); must be set before initialize()
            jax.config.update("jax_cpu_collectives_implementation",
                              cpu_collectives)
            jax.distributed.initialize(
                coordinator_address=self.spec.coordinator,
                num_processes=self.spec.num_processes,
                process_id=self.spec.process_id)

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    def global_mesh(self, axis: str = "ep") -> jax.sharding.Mesh:
        """1-D mesh over every device of every process."""
        return jax.sharding.Mesh(np.array(jax.devices()), (axis,))

    def send(self, tree, sharding, *, kind: str = "tokens",
             sync: bool = False, fanout: int = 1) -> TransportHandle:
        t0 = time.perf_counter()
        if getattr(sharding, "is_fully_addressable", True):
            moved = jax.device_put(tree, sharding)
        else:
            # host-local -> process-global (each process contributes its
            # slice; replicated specs require identical host arrays)
            from jax.experimental import multihost_utils
            moved = multihost_utils.host_local_array_to_global_array(
                tree, sharding.mesh, sharding.spec)
        if sync:
            jax.block_until_ready(moved)
        h = TransportHandle(kind=kind, nbytes=tree_nbytes(tree) * max(1, fanout),
                            issue_s=time.perf_counter() - t0,
                            fanout=fanout, data=moved)
        h.sim_s = self._simulate(h)
        self._account(h)
        return h

    def gather(self, tree):
        """Host-readable view: addressable arrays read directly; global
        arrays read from the first addressable shard (valid for
        replicated outputs — the only global layout the serving paths
        read back on the host)."""

        def to_host(a):
            if getattr(a, "is_fully_addressable", True):
                return np.asarray(a)
            return np.asarray(a.addressable_data(0))

        return jax.tree.map(to_host, tree)


# ------------------------------------------------------------------ registry
TRANSPORTS = {
    "inproc": InProcessTransport,
    "simrdma": SimRdmaTransport,
    "multi": MultiControllerTransport,
}

_DEFAULT: Optional[Transport] = None


def make_transport(name: str, **kwargs) -> Transport:
    """Instantiate a backend by name ('inproc' | 'simrdma' | 'multi')."""
    try:
        cls = TRANSPORTS[name]
    except KeyError:
        raise ValueError(f"unknown transport {name!r}; "
                         f"choose from {sorted(TRANSPORTS)}") from None
    return cls(**kwargs)


def default_transport() -> Transport:
    """Process-wide fallback ``InProcessTransport`` — used by call sites
    (e.g. ``kvcache.migrate_kv``) when no transport is threaded in, so
    legacy callers keep today's behavior with accounting attached."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = InProcessTransport()
    return _DEFAULT
