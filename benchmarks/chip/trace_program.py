"""Run one cell traced, as ``run.py --trace 1`` does, and read the profiler
trace with the program's own spans and named scopes.

    python3 benchmarks/chip/trace_program.py --workload mixtral.decode \
        --seed 7 --seconds 30 [--keep-trace steps.json.gz]

Prints ``run.py``'s result line with, added: ``breakdown.idle_by_span``
(device 0's idle time by the innermost program span open over it),
``breakdown.device_by_scope`` (device time by named scope), ``counters``
(the program's counters over the traced steps) and ``per_step_ms`` (host
time per traced decode step inside each engine span, beside the client's
step time).  ``--keep-trace`` writes the reduced trace, program spans and
scopes included.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import program, run, spec, trace  # noqa: E402


def traced(bench: dict, cell: str, seed: int, seconds: float,
           keep_trace=None, **kw) -> dict:
    import jax
    seen = {}

    def reduce_trace(trace_dir, devices, keep):
        iv = program.load_xplane(trace.find_xplane(str(trace_dir)))
        if keep:
            iv.save(keep)
        seen["red"] = program.ProgramReduced.for_span(
            iv, "bench.traced", devices=[d.id for d in devices])
        return seen["red"]

    reader = spec.reader

    def reading(name):
        fn = reader(name)

        def read(rec, red):
            if "window" not in seen:        # while the engine is alive
                seen["window"] = program.traced_window(rec)
                seen["step_s"] = [s.t1 - s.t0 for s in rec["traced_steps"]]
            return fn(rec, red)
        return read

    def start_trace(log_dir, profiler_options=None, **k):
        # the programs' HLO, for the named scopes of their operations
        profiler_options.enable_hlo_proto = True
        start(log_dir, profiler_options=profiler_options, **k)

    # run.py has no hook for these readings; PERF.md §7 names the edits
    # that would fold them into it
    reduce, start = run.reduce_trace, jax.profiler.start_trace
    run.reduce_trace, spec.reader = reduce_trace, reading
    jax.profiler.start_trace = start_trace
    try:
        out = run.run_cell(bench, cell, seed, seconds, True,
                           keep_trace=keep_trace, **kw)
    finally:
        run.reduce_trace, spec.reader = reduce, reader
        jax.profiler.start_trace = start
    red = seen["red"]
    dev0 = red.devices[0]
    out["breakdown"]["idle_by_span"] = red.idle_by_program_span(dev0)
    out["breakdown"]["device_by_scope"] = red.device_time_by_scope()
    if seen.get("window") is not None:
        totals, counters = seen["window"]
        n = counters["decode_steps"]
        out["counters"] = counters
        out["per_step_ms"] = {k: 1e3 * s / n for k, (c, s) in totals.items()
                              if k.startswith("engine.")}
        out["per_step_ms"]["client_step"] = 1e3 * statistics.fmean(
            seen["step_s"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    try:
        out = traced(spec.load_benchmark(), args.workload, args.seed,
                     args.seconds, keep_trace=args.keep_trace)
    except run.BenchError as e:
        run.log(f"benchmark: FAIL: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
