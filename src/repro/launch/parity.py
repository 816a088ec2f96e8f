"""Serve one workload through ``launch.serve.run`` and keep what a
runtime-parity check needs: per-request token counts, the first decode
step's logits in request order, and which Pallas kernels the runtime's
compiled stage programs hold.

``chip_smoke.py`` compares the monolithic path with the ping-pong
kernel path on the chip with these; the CPU tests do the same at a
reduced size.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from repro.launch.serve import run
from repro.serving.config import ServingConfig

# First-step logits of two runtimes may differ by this fraction of the
# reference's largest |logit|: 2^-4 of it is 8 to 16 bfloat16 ulps at
# that logit.  Both runtimes hold the same bfloat16 weights and accumulate in
# float32, but round intermediates to bfloat16 at different points and
# reduce in different orders, which moves a logit by a few ulps; a wrong
# head, expert or cache slot moves it by O(1).
LOGIT_TOL = 2.0 ** -4


@dataclass
class Served:
    """What one ``run()`` served, reduced to host values."""
    config: ServingConfig
    seconds: float                  # wall time of run(), compiles included
    stats: dict                     # run()'s stats, without the engine
    generated: dict                 # rid -> tokens generated
    first_logits: np.ndarray        # (n_requests, V) f32, rid order
    finite: bool                    # first and last step logits finite
    kernels: dict = field(default_factory=dict)   # stage -> kernel names

    def failures(self) -> list:
        """What went wrong: a request missing or short of its token
        count, or non-finite logits."""
        sc = self.config
        bad = [f"{len(self.generated)}/{sc.n_requests} requests finished"]
        if len(self.generated) == sc.n_requests:
            bad = [f"request {rid}: {n}/{sc.max_new} tokens"
                   for rid, n in sorted(self.generated.items())
                   if n != sc.max_new]
        if not self.finite:
            bad.append("non-finite logits")
        return bad


def kernel_names(hlo_text: str) -> list:
    """Names of the Mosaic kernels (``tpu_custom_call``) in an
    optimized HLO module's text, as ``pallas_call(name=...)`` gave
    them."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.split("=")[0].strip().removeprefix("ROOT ")
            names.add(name.lstrip("%").split(".")[0])
    return sorted(names)


def serve(sc: ServingConfig) -> Served:
    """Serve ``sc`` and release the engine before returning.  Every
    request must decode in the engine's first step: one request per
    batch row, no warm-up requests."""
    if sc.n_requests > sc.max_batch or sc.warmup_requests:
        raise ValueError("parity runs need n_requests <= max_batch and "
                         "no warm-up requests")
    t0 = time.perf_counter()
    stats = run(config=sc)
    seconds = time.perf_counter() - t0
    eng = stats.pop("engine")
    done = sorted(eng.finished, key=lambda r: r.rid)
    rows = [r.slot for r in done]
    first = np.asarray(eng.first_logits, np.float32)[rows]
    last = np.asarray(eng.last_logits, np.float32)
    kernels = {}
    if eng.runtime is not None:
        kernels = {stage: kernel_names(text) for stage, text
                   in eng.runtime.compiled_stages().items()}
    out = Served(config=sc, seconds=seconds, stats=stats,
                 generated={r.rid: len(r.generated) for r in done},
                 first_logits=first,
                 finite=bool(np.isfinite(first).all()
                             and np.isfinite(last).all()),
                 kernels=kernels)
    # drop every reference to this run's weights (the jit caches hold
    # the runtime's stage programs) before the next run allocates its own
    del eng
    gc.collect()
    jax.clear_caches()
    return out


def logits_gap(ref: Served, other: Served) -> float:
    """Largest first-step logit difference, as a fraction of the
    reference's largest |logit| (compare with ``LOGIT_TOL``)."""
    diff = np.abs(ref.first_logits - other.first_logits).max()
    return float(diff / np.abs(ref.first_logits).max())
