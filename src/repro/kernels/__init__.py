"""Pallas TPU kernels for the serving hot path (paper §6 "fused
kernels"): compiled with Mosaic on TPU, and validated in interpret mode
on CPU against the pure-jnp oracles in ``repro.kernels.ref``.

The public API is the jit'd ``ops`` entry points re-exported here — callers
use ``from repro.kernels import grouped_mlp`` (or ``ops.grouped_mlp``)
rather than deep-importing the per-kernel modules.
"""
from repro.kernels.ops import (decode_attention, gating_dispatch,
                               gating_topk, grouped_matmul, grouped_mlp,
                               mla_decode_attention, paged_decode_attention)

__all__ = ["decode_attention", "gating_dispatch", "gating_topk",
           "grouped_matmul", "grouped_mlp", "mla_decode_attention",
           "paged_decode_attention"]
