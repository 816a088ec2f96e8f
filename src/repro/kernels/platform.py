"""Where a Pallas kernel runs: compiled by Mosaic on a TPU backend,
through the Pallas interpreter everywhere else."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool]) -> bool:
    """Resolve a kernel's ``interpret`` argument.

    ``None`` (every kernel's default) interprets exactly when the
    backend is not a TPU.  On a TPU backend a kernel always compiles:
    asking for interpret mode there raises instead of silently running
    the interpreter on the chip.  ``interpret=False`` off-TPU is how the
    compile tests lower kernels for a described (not attached) TPU."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode on a TPU backend: kernels "
                         "always compile on TPU")
    return bool(interpret)
