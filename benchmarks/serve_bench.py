"""End-to-end serving benchmark on CPU at reduced scale: monolithic vs
disaggregated vs ping-pong micro-batched serving (inline and
cluster-disaggregated prefill), batched continuous requests.

On one CPU device the disaggregated runtime cannot show wall-clock
overlap (no parallel hardware) — this benchmark validates correctness of
the full serving path and reports all throughputs plus the ping-pong
runtime's per-stage timing decomposition and the prefill/transfer/decode
phase breakdown; the *modeled* gain is in fig8/fig12.

``python -m benchmarks.serve_bench --out BENCH_serve.json
--baseline-collects 3`` writes the machine-readable baseline used to
track the serving perf trajectory across PRs (three independent
collects merged into per-key minima, so gate floors reflect the
machine's slow windows).  ``--check BENCH_serve.json`` is the CI
perf-regression gate: it exits non-zero when ping-pong-vs-monolithic
speedup or tok/s drops more than ``--tolerance`` (default 15%) below
the committed baseline, after re-measuring flagged configs to rule out
transient noise.  Absolute tok/s is machine-dependent — the committed
baseline must be regenerated on the CI runner class it gates.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import jax

from benchmarks.common import emit
from repro.launch.serve import run as serve_run
from repro.serving.stats import STATS_SCHEMA_VERSION

CONFIGS = (
    ("monolithic", {}),
    ("disagg", {}),
    ("pingpong", {}),
    ("pingpong_m2n", {"use_m2n": True}),
    # every hop priced by the simulated-RDMA transport backend: the
    # recorded per-hop bytes + modeled latency land in the entry's
    # "transport" section (tok/s still gates the real in-process speed
    # — the sim only accounts, it does not sleep)
    ("pingpong_simrdma", {"use_m2n": True, "transport": "simrdma"}),
    # the PR-2 tentpole: prefill on its own cluster, KV rows migrated
    # into the decode cache at admission (async transfer)
    ("pingpong_disagg_prefill", {"prefill_devices": 1, "transfer": "async"}),
    # the PR-3 tentpole: zipf(1.2)-skewed routing, static placement vs
    # live load-balanced placement with hot-expert replication.  The
    # gate floors cover tok/s + speedup; token-identity and the
    # imbalance-vs-static property are asserted by the test suites
    # (single-CPU runs degenerate to one expert node, imbalance 1.0)
    ("pingpong_zipf_static", {"zipf_route_bias": 1.2}),
    ("pingpong_zipf_rebalanced", {"zipf_route_bias": 1.2,
                                  "expert_rebalance_every": 2}),
    # the kernel hot path (flash decode attention + fused
    # gating/dispatch + grouped expert MLP) through the standard
    # ping-pong flow.  Interpret-mode wall clock on this CPU container
    # is far below the jnp path's — the gate tracks it as its own entry
    # so the kernel path can't silently rot (parity is asserted by
    # tests/test_disagg_kernels.py / test_multidevice.py)
    ("pingpong_kernels", {"use_kernels": True}),
    # the PR-6 tentpole: paged KV layout — engine-level gather/write-back
    # over a refcounted page pool.  Random prompts, so the radix tree
    # only ever misses; this entry prices the paging overhead itself
    ("pingpong_paged", {"kv_layout": "paged", "page_size": 8}),
    # shared-system-prompt workload (24 of 32 prompt tokens shared):
    # radix prefix hits skip re-prefilling the shared pages — the
    # entry's prefix_cache section records the hit rate and the phases
    # section the shrunken prefill
    ("pingpong_prefix_shared", {"kv_layout": "paged", "page_size": 8,
                                "prompt_len": 32, "shared_prefix_len": 24}),
)

PHASE_KEYS = ("prefill_s", "transfer_s", "decode_s", "prefills",
              "transfer_n", "transfer_mode", "prefill_batches")
# live expert-balance report (present for runtimes with a disagg handle)
BALANCE_KEYS = ("imbalance", "rebalances", "replicated_experts",
                "rebalance_s")
# gate tolerances are relative drops vs the committed baseline
CHECKED_KEYS = ("decode_tok_per_s", "vs_monolithic")


WORKLOAD = dict(use_reduced=True, n_requests=6, max_new=4, max_batch=4,
                max_seq=64, microbatches=2, prompt_len=8,
                warmup_requests=2, verbose=False)


def _serve_once(name: str, extra: dict) -> dict:
    runtime = "pingpong" if name.startswith("pingpong") else name
    kw = {**WORKLOAD, **extra}      # entries may override workload knobs
    try:
        stats = serve_run("mixtral-8x22b", runtime=runtime, **kw)
        del stats["engine"]     # nothing here reads it; let it be freed
        return stats
    finally:
        # every run builds a fresh engine/runtime (per-instance jits;
        # warmup_requests absorbs the recompile before timing), so
        # nothing is reused across runs — but dead executables pin LLVM
        # JIT code pages and a long --baseline-collects sweep exhausts
        # vm.max_map_count ("LLVM compilation error: Cannot allocate
        # memory").  Drop them eagerly to bound the map count at ~1 run.
        gc.collect()
        jax.clear_caches()


def _entry(best: dict, runs: list) -> dict:
    entry = {k: best[k] for k in ("tokens", "decode_iters", "wall_s",
                                  "decode_tok_per_s", "finished")}
    entry["use_kernels"] = bool(best.get("use_kernels", False))
    entry["kv_layout"] = best.get("kv_layout", "contiguous")
    entry["tok_per_s_runs"] = runs
    # paged layout: page-pool occupancy + radix hit/miss accounting
    for section in ("kv_pages", "prefix_cache"):
        if section in best:
            entry[section] = best[section]
    entry["phases"] = {k: best["phases"][k] for k in PHASE_KEYS
                       if k in best["phases"]}
    entry.update({k: best[k] for k in BALANCE_KEYS if k in best})
    if "stages" in best:
        entry["stages"] = {k: v for k, v in best["stages"].items()
                           if k in ("t_a", "t_e", "t_c")}
    if "transport" in best:
        # per-hop wire accounting from the run's transport backend
        # (kinds: tokens / kv / weights / collective)
        entry["transport"] = best["transport"]
    return entry


def _measure(name: str, extra: dict, repeats: int) -> dict:
    """Serve one config ``repeats`` times, return the best run (highest
    tok/s)."""
    best, runs = None, []
    for _ in range(max(1, repeats)):
        stats = _serve_once(name, extra)
        runs.append(stats["decode_tok_per_s"])
        if best is None or stats["decode_tok_per_s"] > \
                best["decode_tok_per_s"]:
            best = stats
    return _entry(best, runs)


def _add_speedups(results: dict) -> dict:
    mono = results["monolithic"]["decode_tok_per_s"]
    for name in results:
        results[name]["vs_monolithic"] = (
            results[name]["decode_tok_per_s"] / max(mono, 1e-9))
    return results


def collect(repeats: int = 3) -> dict:
    """Best-of-``repeats`` per config, measured ROUND-ROBIN (all configs
    once, then all again, ...), keeping each config's fastest run.

    The workload is deterministic (greedy, fixed seed, pinned prompt
    length — one prefill shape to compile), so best-of-N measures
    steady-state speed: the first round absorbs compile time and
    discarded rounds absorb co-tenant/thermal noise — single-run
    variance on shared CPU runners exceeds the gate's 15% tolerance.
    Round-robin matters for the speedup ratios: every config samples the
    same machine-speed windows, so a slow spell hits numerator and
    denominator alike instead of distorting ``vs_monolithic``."""
    best = {name: None for name, _ in CONFIGS}
    runs = {name: [] for name, _ in CONFIGS}
    for _ in range(max(1, repeats)):
        for name, extra in CONFIGS:
            stats = _serve_once(name, extra)
            runs[name].append(stats["decode_tok_per_s"])
            if best[name] is None or stats["decode_tok_per_s"] > \
                    best[name]["decode_tok_per_s"]:
                best[name] = stats
    return _add_speedups(
        {name: _entry(best[name], runs[name]) for name, _ in CONFIGS})


def combine_baselines(collects: list) -> dict:
    """Merge several independent ``collect()`` results into one
    conservative baseline: each gated key records the *minimum* across
    collects (the machine's slow windows), so gate floors tolerate
    machine-speed swings while a real regression — below even the worst
    historical window minus tolerance — still fails.  Descriptive fields
    come from the last collect."""
    out = {}
    for name in collects[-1]:
        entries = [c[name] for c in collects]
        e = dict(entries[-1])
        for key in CHECKED_KEYS:
            e[key] = min(x[key] for x in entries)
        e["tok_per_s_runs"] = [r for x in entries
                               for r in x["tok_per_s_runs"]]
        out[name] = e
    return out


def _describe_baseline(baseline: dict, name: str) -> str:
    """One-line provenance of a committed baseline entry: the machine
    class / workload it was recorded on plus the entry's keys — printed
    instead of dying with a bare KeyError when the gated key set has
    drifted between the fresh code and the committed JSON."""
    wl = baseline.get("workload", {})
    machine = {k: wl[k] for k in ("device", "arch") if k in wl}
    entry_keys = sorted(baseline["results"].get(name, {}))
    base_ver = baseline.get("stats_schema_version", 1)
    return (f"baseline recorded on {machine or 'unknown machine class'} "
            f"with stats schema v{base_ver} (code is "
            f"v{STATS_SCHEMA_VERSION}); {name!r} entry keys: {entry_keys}")


def check(fresh: dict, baseline: dict, tolerance: float = 0.15) -> list:
    """Compare a fresh ``collect()`` result against the committed
    baseline payload.  Returns ``(config_name, message)`` regression
    tuples (empty = gate passes).  New configs absent from the baseline
    pass by construction; configs *removed* from the fresh run fail.
    A gated key missing from the committed baseline (schema drift: the
    code gained a metric the JSON predates) is reported with the
    baseline's provenance and skipped instead of dying with a bare
    KeyError — regenerate the baseline to realign.  A gated key missing
    from the *fresh* run is a code regression and fails."""
    failures = []
    for name, base in baseline["results"].items():
        got = fresh.get(name)
        if got is None:
            failures.append((name, f"{name}: present in baseline, missing "
                                   f"from fresh run"))
            continue
        for key in CHECKED_KEYS:
            if name == "monolithic" and key == "vs_monolithic":
                continue  # identically 1.0
            if key not in got:
                # the fresh run must always emit every gated key — a
                # missing one is a code regression, not schema drift
                failures.append(
                    (name, f"{name}.{key}: missing from fresh run "
                           f"({_describe_baseline(baseline, name)})"))
                continue
            if key not in base:
                print(f"serve_bench --check: key {name}.{key} missing from "
                      f"baseline — {_describe_baseline(baseline, name)}; "
                      f"skipping this key (regenerate the baseline to "
                      f"realign)", file=sys.stderr)
                continue
            floor = base[key] * (1.0 - tolerance)
            if got[key] < floor:
                failures.append(
                    (name, f"{name}.{key}: {got[key]:.3f} < {floor:.3f} "
                           f"(baseline {base[key]:.3f} - {tolerance:.0%})"))
    return failures


def check_with_retries(results: dict, baseline: dict, tolerance: float,
                       repeats: int, max_retries: int = 3) -> list:
    """Gate with noise confirmation: configs flagged by ``check`` are
    re-measured (keeping each config's best observation) before the
    verdict — a transient co-tenant/thermal dip must survive
    ``max_retries`` extra best-of-``repeats`` rounds to fail the gate,
    while a real regression fails every round.  Re-measuring can also
    *newly* flag a config (a monolithic retry raises every speedup
    denominator), which the next round then re-measures — one reason
    the retry budget is 3, not 1.  Mutates ``results`` with the
    improved observations.  Returns the final failure list."""
    by_name = dict(CONFIGS)
    failures = check(results, baseline, tolerance)
    for _ in range(max_retries):
        # only numeric regressions can be measurement noise; structural
        # failures (config/key missing from the fresh run) are
        # deterministic and re-measuring cannot fix them
        flagged = {name for name, msg in failures
                   if name in by_name and "missing" not in msg}
        if not flagged:
            break
        print(f"retrying flagged configs to rule out noise: "
              f"{sorted(flagged)}", file=sys.stderr)
        for name in sorted(flagged):
            entry = _measure(name, by_name[name], repeats)
            if entry["decode_tok_per_s"] > results[name]["decode_tok_per_s"]:
                entry["tok_per_s_runs"] = (results[name]["tok_per_s_runs"]
                                           + entry["tok_per_s_runs"])
                results[name] = entry
        _add_speedups(results)
        failures = check(results, baseline, tolerance)
    return failures


def run():
    # benchmarks.run smoke entry: single repeat (the --check gate is the
    # statistically careful consumer)
    results = collect(repeats=1)
    for name, r in results.items():
        extra = (f", imbalance={r['imbalance']:.2f}"
                 f" ({r.get('rebalances', 0)} rebalances)"
                 if "imbalance" in r else "")
        emit(f"serve_{name}", 1e6 / max(r["decode_tok_per_s"], 1e-9),
             f"{r['tokens']} tokens, {r['decode_iters']} decode iters, "
             f"{r['decode_tok_per_s']:.1f} tok/s, "
             f"{r['vs_monolithic']:.2f}x vs monolithic{extra} "
             f"(reduced mixtral, CPU)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="write results as JSON (e.g. BENCH_serve.json)")
    ap.add_argument("--check", default=None, metavar="BASELINE_JSON",
                    help="perf-regression gate: exit non-zero if speedup "
                         "or tok/s dropped below the committed baseline")
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="allowed relative drop vs baseline (default 0.15)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="runs per config; best run is recorded/gated")
    ap.add_argument("--baseline-collects", type=int, default=1,
                    help="independent collect() passes merged into a "
                         "conservative (per-key minimum) baseline — use "
                         ">=3 when regenerating the committed "
                         "BENCH_serve.json so gate floors reflect the "
                         "machine's slow windows, not one snapshot")
    args = ap.parse_args()
    n_collects = max(1, args.baseline_collects)
    collects = [collect(repeats=args.repeats) for _ in range(n_collects)]
    results = collects[0] if n_collects == 1 else combine_baselines(collects)
    if n_collects > 1:
        print(f"combined {n_collects} collects into conservative "
              f"per-key-minimum baseline")
    failures = []
    if args.check:
        with open(args.check) as f:
            baseline = json.load(f)
        failures = check_with_retries(results, baseline, args.tolerance,
                                      args.repeats)
    for name, r in results.items():
        extra = (f", imbalance={r['imbalance']:.2f}"
                 if "imbalance" in r else "")
        print(f"{name}: {r['decode_tok_per_s']:.1f} tok/s "
              f"({r['vs_monolithic']:.2f}x vs monolithic{extra})")
    if args.out:
        payload = {
            "benchmark": "serve_bench",
            # version of Engine.stats() these entries were derived from
            # (serving.stats.STATS_SCHEMA_VERSION) — --check prints both
            # versions when diagnosing baseline schema drift
            "stats_schema_version": STATS_SCHEMA_VERSION,
            "workload": {"arch": "mixtral-8x22b", "device": "cpu",
                         **{k: v for k, v in WORKLOAD.items()
                            if k != "verbose"}},
            "results": results,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}")
    if args.check:
        if failures:
            print(f"PERF REGRESSION vs {args.check}:", file=sys.stderr)
            for _, line in failures:
                print(f"  {line}", file=sys.stderr)
            raise SystemExit(1)
        print(f"perf gate vs {args.check}: OK "
              f"(tolerance {args.tolerance:.0%}, best of {args.repeats}+ "
              f"runs per config)")


if __name__ == "__main__":
    main()
