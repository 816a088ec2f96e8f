"""Typed serving configuration.

One ``ServingConfig`` dataclass replaces the flag sprawl that used to be
spread across ``launch/serve.py`` argparse flags and the ``Engine(...)``
constructor's keyword arguments.  The launcher builds it with
``ServingConfig.from_args`` and threads it everywhere; the engine takes
it as ``Engine(cfg, params, config=...)`` (the old scalar kwargs are
still accepted as deprecated aliases for one release).
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass, fields, replace
from typing import Union

from repro.serving.sampler import SamplingParams

RUNTIMES = ("monolithic", "disagg", "pingpong")
DTYPES = ("float32", "bfloat16")
TRANSFERS = ("sync", "async")
ENGINE_MODES = ("monolithic", "pingpong")
KV_LAYOUTS = ("contiguous", "paged")


@dataclass
class ServingConfig:
    """Everything scalar about how a serving run is set up.

    Launcher-level fields (workload shape, cluster split) and
    engine-level fields (batching, sampling, rebalancing) live together
    so one object describes a run end to end; ``to_engine_kwargs()``
    projects out the engine's slice.
    """
    # ---- workload / launcher ------------------------------------------
    arch: str = "mixtral-8x22b"
    use_reduced: bool = True
    n_layers: int = 0                  # >0 cuts depth; widths stay as given
    lead_layers: int = 0               # >0 keeps this many leading layers
    experts_held: int = 0              # >0 routed experts held a MoE layer
    vocab: int = 0                     # >0 serves this slice of the vocabulary
    dtype: str = "float32"             # weights + KV cache: float32 | bfloat16
    runtime: str = "monolithic"        # monolithic | disagg | pingpong
    n_requests: int = 8
    max_new: int = 8
    prompt_len: int = 0                # 0 = random lengths
    warmup_requests: int = 0
    zipf_route_bias: float = 0.0
    verbose: bool = True
    # ---- decode runtime ------------------------------------------------
    microbatches: Union[int, str] = 3  # int, or "auto" (paper eq. 3)
    use_m2n: bool = False
    use_kernels: bool = False          # Pallas hot-path kernels
    profile_stages: bool = False
    # ---- transport / clusters (paper §3-§4) ----------------------------
    transport: str = "inproc"          # inproc | simrdma | multi
    prefill_devices: int = 0
    transfer: str = "async"            # KV migration: sync | async
    prefill_chunk_tokens: int = 512
    # ---- KV cache layout (paged subsystem) ------------------------------
    kv_layout: str = "contiguous"      # contiguous | paged
    page_size: int = 16                # token slots per KV page (paged)
    kv_pool_pages: int = 0             # 0 = auto-size from max_batch/max_seq
    prefix_cache: bool = True          # radix prefix reuse (paged only)
    shared_prefix_len: int = 0         # workload: shared system-prompt tokens
    # ---- engine ---------------------------------------------------------
    max_batch: int = 4
    max_seq: int = 128
    seed: int = 0
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    expert_rebalance_every: int = 0
    expert_replication: bool = True
    expert_window: int = 8

    def __post_init__(self):
        self.validate()

    def validate(self) -> "ServingConfig":
        if self.dtype not in DTYPES:
            raise ValueError(f"dtype must be one of {DTYPES}, "
                             f"got {self.dtype!r}")
        for name in ("n_layers", "lead_layers", "experts_held", "vocab"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0, got "
                                 f"{getattr(self, name)}")
        if self.runtime not in RUNTIMES:
            raise ValueError(f"runtime must be one of {RUNTIMES}, "
                             f"got {self.runtime!r}")
        if self.transfer not in TRANSFERS:
            raise ValueError(f"transfer must be one of {TRANSFERS}, "
                             f"got {self.transfer!r}")
        from repro.core.transport import TRANSPORTS
        if self.transport not in TRANSPORTS:
            raise ValueError(f"transport must be one of "
                             f"{sorted(TRANSPORTS)}, got {self.transport!r}")
        if self.microbatches != "auto":
            self.microbatches = int(self.microbatches)
        if self.kv_layout not in KV_LAYOUTS:
            raise ValueError(f"kv_layout must be one of {KV_LAYOUTS}, "
                             f"got {self.kv_layout!r}")
        if self.kv_layout == "paged":
            if self.page_size <= 0:
                raise ValueError(f"page_size must be positive, "
                                 f"got {self.page_size}")
            if self.max_seq % self.page_size:
                raise ValueError(f"max_seq={self.max_seq} must be a whole "
                                 f"number of pages of {self.page_size}")
        return self

    @property
    def n_pool_pages(self) -> int:
        """Page-pool size: explicit, or auto — enough for every batch
        row plus two spare rows' worth of pages so the prefix cache can
        retain recently finished chains without starving admission."""
        if self.kv_pool_pages:
            return self.kv_pool_pages
        return (self.max_batch + 2) * (self.max_seq // self.page_size)

    # ----------------------------------------------------------- projections
    @property
    def engine_mode(self) -> str:
        """The engine mode implied by the launcher runtime choice."""
        return "pingpong" if self.runtime == "pingpong" else "monolithic"

    def sampling_params(self) -> SamplingParams:
        return SamplingParams(temperature=self.temperature,
                              top_k=self.top_k, top_p=self.top_p)

    def to_engine_kwargs(self) -> dict:
        """The ``Engine(cfg, params, **config.to_engine_kwargs())``
        handoff: the whole config rides along as ``config=``.  Object
        wiring (runtime instance, prefill worker, transport instance,
        kv sharding) stays with the launcher — it owns those objects."""
        return {"config": self}

    # -------------------------------------------------------------- argparse
    # argparse dest -> config field, where the names differ
    _ARG_ALIASES = {"requests": "n_requests", "reduced": "use_reduced",
                    "kernels": "use_kernels"}

    @classmethod
    def from_args(cls, args: argparse.Namespace) -> "ServingConfig":
        """Build from a parsed ``launch.serve`` argument namespace: every
        namespace entry that names (or aliases) a config field is taken,
        unknown entries are ignored (they belong to the launcher)."""
        known = {f.name for f in fields(cls)}
        kw = {}
        for dest, val in vars(args).items():
            name = cls._ARG_ALIASES.get(dest, dest)
            if name in known and val is not None:
                kw[name] = val
        if kw.get("arch") is None:
            kw.pop("arch", None)
        return cls(**kw)

    def with_overrides(self, **kw) -> "ServingConfig":
        return replace(self, **kw)
