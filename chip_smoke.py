"""Chip smoke test: serve mixtral-8x22b at its published widths on a TPU.

Drives the serving main path, ``repro.launch.serve.run``, at every
published width of mixtral-8x22b (d_model 6144, 48 query / 8 kv heads of
128, 8 experts of width 16384, top-2, vocab 32000), cut to one layer (the
model's whole layer pattern), in bfloat16 on weights drawn from a seed:

  (a) the monolithic jnp decode path, on one chip;
  (b) the ping-pong runtime on the Pallas kernels, m = 2 micro-batches,
      on one chip — or, with ``--four-chips``, with attention on two
      chips and the 8 experts on the other two (4 each), M2N dispatch.

It checks that both serve every request its token count with finite
logits, that their first decode step's logits agree within
``repro.launch.parity.LOGIT_TOL``, and that (b)'s compiled stage
programs hold the decode-attention, gating/dispatch and grouped-matmul
Mosaic kernels.  It prints its findings, then, as its last line, one
JSON object naming the device.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host of four chips

With no TPU attached, or outside a checkout of this repository, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "mixtral-8x22b"
N_LAYERS = 1


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="attention on 2 chips, experts on 2, M2N dispatch")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1

    from repro.config import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.parity import LOGIT_TOL, logits_gap, serve
    from repro.serving.config import ServingConfig
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"no TPU: JAX's first device is {dev.platform}")
    if len(devices) != n_chips:
        return _fail(f"needs {n_chips} chip(s), JAX sees {len(devices)}")
    print(f"cache: {enable_compile_cache(ROOT)}")

    base = ServingConfig(arch=ARCH, use_reduced=False, n_layers=N_LAYERS,
                         dtype="bfloat16", n_requests=8, max_new=4,
                         max_batch=8, max_seq=128, prompt_len=16, seed=0,
                         microbatches=2)
    runs = {"a": base.with_overrides(runtime="monolithic"),
            "b": base.with_overrides(runtime="pingpong", use_kernels=True,
                                     use_m2n=args.four_chips)}
    cfg = get_config(ARCH)
    print(f"{ARCH}: d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads}x{cfg.resolved_head_dim} experts="
          f"{cfg.moe.n_experts}x{cfg.moe.d_ff_expert} top-{cfg.moe.top_k} "
          f"vocab={cfg.vocab} | layers={N_LAYERS}/{cfg.n_layers} "
          f"dtype={base.dtype} | {base.n_requests} requests x "
          f"{base.max_new} tokens, prompt {base.prompt_len} | "
          f"{dev.device_kind} x{len(devices)}")

    served, failures = {}, []
    for name, sc in runs.items():
        s = served[name] = serve(sc)
        mem = [d.memory_stats() for d in devices]
        peaks = [m["peak_bytes_in_use"] / 1e9 for m in mem]
        live = [m["bytes_in_use"] / 1e9 for m in mem]
        print(f"({name}) {sc.runtime}{'+kernels' if sc.use_kernels else ''}"
              f"{'+m2n' if sc.use_m2n else ''}: "
              f"{s.stats['finished']} requests, {s.stats['tokens']} tokens, "
              f"{s.seconds:.1f}s with compiles "
              f"({s.stats['decode_tok_per_s']:.1f} tok/s timed) | GB per "
              f"chip: peak so far {' '.join(f'{p:.3f}' for p in peaks)}, "
              f"in use after {' '.join(f'{x:.3f}' for x in live)}")
        failures += [f"({name}) {msg}" for msg in s.failures()]

    b = served["b"]
    want = {"attn": ["decode_attention"], "expert": ["grouped_matmul"]}
    # gating/dispatch runs with attention, or on the expert shards (M2N)
    want["expert" if args.four_chips else "attn"].append("gating_dispatch")
    for stage, names in want.items():
        have = b.kernels.get(stage, [])
        print(f"(b) compiled {stage} stage kernels: {have}")
        missing = sorted(set(names) - set(have))
        if missing:
            failures.append(f"(b) {stage} stage lacks Mosaic kernels "
                            f"{missing}")
    gap = logits_gap(served["a"], b)
    print(f"first-step logits: max|a-b| / max|a| = {gap!r} "
          f"(tolerance {LOGIT_TOL}), max|a| = "
          f"{float(abs(served['a'].first_logits).max())!r}")
    if not gap <= LOGIT_TOL:
        failures.append(f"first-step logits differ by {gap!r} of the "
                        f"largest logit (> {LOGIT_TOL})")
    if failures:
        for f in failures:
            _fail(f)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
