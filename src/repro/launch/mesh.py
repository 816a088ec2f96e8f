"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches JAX device state — the dry-run must set XLA_FLAGS
before the first device query.
"""
from __future__ import annotations

import jax


def _axis_type_kwargs(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips for multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **_axis_type_kwargs(len(axes)))


def split_serving_devices(n_prefill: int, devices=None):
    """Disjoint prefill / decode device groups for disaggregated serving
    (paper §3: prefill and decode get their own clusters).

    Reserves the *last* ``n_prefill`` local devices for the prefill
    cluster and leaves the rest to the decode cluster, whose further
    attention/expert split happens inside
    ``core.disagg.DisaggregatedInstance``.  Returns
    ``(prefill_devices, decode_devices)``.

    Degenerate cases: ``n_prefill <= 0`` returns an empty prefill group
    (inline prefill); when ``n_prefill`` would leave decode empty (e.g.
    a single-device CPU smoke run) both clusters share the full pool —
    a correctness-preserving overlap fallback.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if n_prefill <= 0:
        return [], devs
    if n_prefill < len(devs):
        return devs[-n_prefill:], devs[:-n_prefill]
    return devs, devs


def data_axes(mesh: jax.sharding.Mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: jax.sharding.Mesh) -> str:
    assert "model" in mesh.axis_names
    return "model"
