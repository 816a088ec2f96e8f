"""Public entry points of the Pallas kernels.

On a TPU backend every kernel compiles with Mosaic; asking for
interpret mode there raises (``kernels.platform.interpret_mode``).  Off
TPU the kernels run in the Pallas interpreter, which is how the CPU
tests check them against the ``ref.py`` oracles.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention,
                                            mla_decode_attention,
                                            paged_decode_attention)
from repro.kernels.gating_topk import gating_dispatch, gating_topk
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models.common import activation

__all__ = ["decode_attention", "gating_dispatch", "gating_topk",
           "grouped_matmul", "grouped_mlp", "mla_decode_attention",
           "paged_decode_attention"]


def grouped_mlp(xe, w1, w3, w2, act: str = "silu", row_valid=None, **kw):
    """Per-expert gated MLP built from three grouped matmuls.

    xe: (E, C, d) expert token buffers -> (E, C, d).

    row_valid: optional (E, C) bool — the capacity-drop-aware variant for
    ``capacity_mode != 'full'``: rows holding a dropped/empty capacity
    slot are forced to exact zeros on output, so the combine scatter sees
    zeros even for activations with ``act(0) != 0``.
    """
    h = activation(grouped_matmul(xe, w1, **kw).astype(jnp.float32), act)
    h = h * grouped_matmul(xe, w3, **kw).astype(jnp.float32)
    out = grouped_matmul(h.astype(xe.dtype), w2, **kw)
    if row_valid is not None:
        out = out * row_valid[..., None].astype(out.dtype)
    return out
