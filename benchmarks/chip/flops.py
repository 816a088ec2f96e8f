"""The least time the chip needs for counted work.

The counts themselves depend on the model's shape, and each family's
reference module gives them (``references/__init__.py``)."""
from __future__ import annotations


def roofline_time(flops: float, nbytes: float, peaks: dict):
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
