"""Grouped (per-expert) matmul Pallas TPU kernel.

This is the MoE expert hot loop under expert parallelism: each expert
runs a *complete* GEMM over its aggregated token buffer — the property
the paper exploits (EP keeps GEMMs whole, unlike TP which splits them).

Tiling: grid (G, M/Mb, N/Nb, K/Kb); the K dimension is innermost so the
f32 accumulator tile stays resident in VMEM across K steps (output
revisiting — the out BlockSpec ignores the K index).  Tile sizes default
to MXU-aligned multiples of 128 and are shrunk automatically for small
inputs so the same kernel serves smoke-scale tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode


def _tile(dim: int, want: int) -> int:
    """Largest divisor of ``dim`` that is <= want (prefer MXU multiples)."""
    t = min(dim, want)
    while dim % t:
        t -= 1
    return t


def _kernel(x_ref, w_ref, o_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("mb", "nb", "kb", "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, *, mb: int = 128,
                   nb: int = 128, kb: int = 512,
                   interpret: Optional[bool] = None) -> jax.Array:
    """(G, M, K) @ (G, K, N) -> (G, M, N) per-group matmul.

    VMEM working set per step: Mb*Kb + Kb*Nb (bf16) + Mb*Nb (f32 acc);
    defaults (128, 128, 512) use ~0.3 MB — far under the ~16 MB/core VMEM
    budget, leaving room for double buffering.
    """
    G, M, K = x.shape
    _, _, N = w.shape
    Mb, Nb, Kb = _tile(M, mb), _tile(N, nb), _tile(K, kb)
    grid = (G, M // Mb, N // Nb, K // Kb)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Mb, Kb), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, Kb, Nb), lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, Mb, Nb), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), jnp.float32),
        interpret=interpret_mode(interpret),
        name="grouped_matmul",
    )(x, w)
    return out.astype(x.dtype)
