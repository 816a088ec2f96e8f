"""Run one benchmark cell once and print its result as one JSON line.

    python3 benchmarks/chip/run.py --workload mixtral.decode --seed 7 \
        --seconds 30 --trace 0

Set-up builds the deployment through the serving launcher
(``repro.launch.serve.run``, which draws the weights on the device from
the seed), warms the prefill of every prompt length the traffic mix can
send and the decode step, and fills the batch with the mix's first
requests.  The window then drives the engine's public ``submit`` /
``step`` for ``--seconds`` seconds as a closed loop of ``max_batch``
clients, each sending its next request as soon as its previous one has
finished.  A token's time is the first host time, after a ``step()``
returns, at which the client sees it.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
a few seconds of the window with the profiler and reports the per-layer
metrics, the device's busy time and a breakdown.  Either way, once the
window has closed and the program's state is freed, a float32 reference
recomputes a sample of the served requests and decides ``correct``: the
module ``references/<name>.py`` that the configuration file names under
``"reference"``, which also gives the sizes and the work counts the
readers take.  With no TPU, or fewer chips than the cell asks for, the run
fails and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import generator, spec  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
TRACE_DIR = ROOT / ".bench_trace"


BenchError = spec.BenchError


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def p95(xs: List[float]) -> float:
    """95th percentile, as ``statistics.quantiles(..., n=20)`` gives it."""
    if len(xs) < 2:
        raise BenchError(f"a 95th percentile needs 2 samples, have {len(xs)}")
    return statistics.quantiles(xs, n=20)[-1]


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def program_seed(seed: int) -> int:
    """The launcher seeds NumPy's legacy generator, which takes 32 bits."""
    return int(seed) % (1 << 32)


def enable_compile_cache(jax):
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program however short
    its compile, eager operations included."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts the programs JAX compiles or loads from the persistent cache
    while ``armed``."""

    def __init__(self, jax):
        self.armed = False
        self.compiles = 0
        self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if self.armed and event == BACKEND_COMPILE:
            self.compiles += 1

    def _ev(self, event, **kw):
        if self.armed and event == CACHE_HIT:
            self.loads += 1


def check_program_config(model_cfg, ref, dims):
    """The program must serve the sizes the configuration file states, as
    the reference module ``ref`` reads both."""
    have, want = ref.program_sizes(model_cfg), ref.sizes(dims)
    bad = {k: (have.get(k), want.get(k))
           for k in sorted(have.keys() | want.keys())
           if have.get(k) != want.get(k)}
    if bad:
        raise BenchError(f"the program serves other sizes than the "
                         f"configuration file (program, file): {bad}")


def build_engine(cell: dict, config: dict, seed: int):
    from repro.launch.serve import run as serve
    from repro.serving.config import ServingConfig
    sc = ServingConfig(**config["program"], **cell["serving"],
                       seed=program_seed(seed), n_requests=0,
                       warmup_requests=0, verbose=False, temperature=0.0)
    return serve(config=sc)["engine"]


def add_spans(eng):
    """Host spans around the engine's admission, retirement and decode
    call, placed from the benchmark's side."""
    import jax

    def span(name: str, fn: Callable) -> Callable:
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    eng._admit = span("bench.admit", eng._admit)
    eng._retire = span("bench.retire", eng._retire)
    if eng.mode == "pingpong":
        eng.runtime.decode_microbatched = span(
            "bench.decode", eng.runtime.decode_microbatched)
    else:
        eng._decode = span("bench.decode", eng._decode)


# --------------------------------------------------------------------------
# the closed loop
# --------------------------------------------------------------------------

@dataclass
class Sent:
    req: object                 # serving.engine.Request
    t_sent: float
    stamps: List[float] = field(default_factory=list)
    t_done: float = 0.0


@dataclass
class StepRecord:
    t0: float
    t1: float
    occupancy: int              # requests with a first token, unfinished
    prefill: List[int]          # prompt lengths whose first token came
    decode_ctx: List[int]       # positions each decoded token attended


class Loop:
    """The clients of a run.  Closed loop: a client sends its next request
    as soon as the client sees its previous one finished.  Open loop:
    requests are sent at the mix's scheduled times, counted from the
    window's opening, and a request's latency counts from that time."""

    def __init__(self, eng, traffic: generator.Traffic):
        self.eng, self.traffic = eng, traffic
        self.live: List[Sent] = []
        self.done: List[Sent] = []
        self.steps: List[StepRecord] = []
        self.sent = 0
        self.schedule: List[float] = []

    def send(self, t_sent: float):
        from repro.serving.engine import Request
        s = self.traffic.next()
        req = Request(rid=s.index, prompt=self.traffic.tokens(s),
                      max_new_tokens=s.max_new)
        self.eng.submit(req)
        self.live.append(Sent(req, t_sent))
        self.sent += 1

    def send_due(self, now: float):
        while self.schedule and self.schedule[0] <= now:
            self.send(self.schedule.pop(0))

    def step(self) -> StepRecord:
        import jax
        t0 = time.perf_counter()
        self.send_due(t0)
        with jax.profiler.TraceAnnotation("bench.step"):
            self.eng.step()
        now = time.perf_counter()
        prefill, ctx, still, finished = [], [], [], 0
        for s in self.live:
            gen, P = s.req.generated, len(s.req.prompt)
            for j in range(len(s.stamps), len(gen)):
                s.stamps.append(now)
                if j == 0:
                    prefill.append(P)
                else:
                    ctx.append(P + j)
            if s.req.t_done:
                s.t_done = now
                self.done.append(s)
                finished += 1
            else:
                still.append(s)
        self.live = still
        if not self.traffic.open_loop:
            for _ in range(finished):
                self.send(now)
        occ = sum(1 for s in self.live if s.stamps)
        rec = StepRecord(t0, now, occ, prefill, ctx)
        self.steps.append(rec)
        return rec


def warm_up(eng, traffic: generator.Traffic, seed: int):
    """Compile every shape the window uses: the prefill of each prompt
    length the mix can send (and the admission copy, the first-token
    sample, retirement) and the decode step at ``max_batch``."""
    from repro.serving.engine import Request
    import numpy as np
    rng = np.random.default_rng([seed, 3])
    for i, L in enumerate(traffic.prompt_lengths):
        eng.submit(Request(rid=-1 - i, max_new_tokens=2,
                           prompt=rng.integers(0, traffic.vocab, L).tolist()))
        while eng.outstanding:
            eng.step()


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------

def end_to_end(loop: Loop, t0: float, t1: float, chips: int,
               names: List[str]) -> dict:
    """The client-side metrics of the window [t0, t1] that ``names`` asks
    for: every token delivered, every first token, every gap between two
    tokens of a request that both arrived in the window."""
    reqs = loop.done + loop.live
    tokens = sum(1 for s in reqs for t in s.stamps if t0 < t <= t1)
    ttft = [s.stamps[0] - s.t_sent for s in reqs
            if s.stamps and t0 < s.stamps[0] <= t1]
    itl = [b - a for s in reqs for a, b in zip(s.stamps, s.stamps[1:])
           if t0 < a and b <= t1]
    log(f"in the window: {tokens} tokens, {len(ttft)} first tokens, "
        f"{len(itl)} gaps between tokens")
    metric = {"tok_s_per_chip": lambda: tokens / (t1 - t0) / chips,
              "ttft_p95_ms": lambda: p95(ttft) * 1e3,
              "itl_p95_ms": lambda: p95(itl) * 1e3}
    return {n: metric[n]() for n in names if n in metric}


def pick_sample(served: List[Sent], seed: int, want_tokens: int,
                max_requests: int) -> List[Sent]:
    """A sample drawn from the seed of the requests that were served tokens
    by the close of the window, finished or not, the one with the most
    served tokens always in it."""
    import numpy as np
    served = [s for s in served if s.req.generated]
    if not served:
        return []
    longest = max(served, key=lambda s: (len(s.req.generated), -s.req.rid))
    rest = [s for s in served if s is not longest]
    order = np.random.default_rng([seed, 4]).permutation(len(rest))
    out, n = [longest], len(longest.req.generated)
    for i in order:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].req.generated)
    return out


def compare(ref, dims, seed: int, sample, max_seq: int, tie: float,
            control: bool, dtype: str) -> dict:
    """Widest gap by which a served token's logit lies below the
    reference's best logit, over the served tokens of the sample whose
    choice of experts the reference does not make within ``tie`` of a tie
    (there, rounding alone may pick other experts, and a token's logits
    then move by whole units).  With ``control``, the same for the float8
    pass's first choices."""
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip.reference import served_gaps
    w = ref.init_weights(dims, program_seed(seed), dtype)
    gaps, lows, margins = [], [], []
    for prompt, gen in sample:
        seq = list(prompt) + list(gen)
        P, G = len(prompt), len(gen)
        if len(seq) - 1 > max_seq:
            raise BenchError(f"a served sequence of {len(seq)} exceeds "
                             f"max_seq {max_seq}")
        toks = np.zeros((max_seq,), np.int32)
        toks[:len(seq) - 1] = seq[:-1]
        tgt = np.zeros((max_seq,), np.int32)
        tgt[:len(seq) - 1] = seq[1:]
        gap, low, margin = served_gaps(
            ref.forward, w, dims, jnp.asarray(toks), jnp.asarray(tgt), control)
        served = slice(P - 1, P - 1 + G)
        gaps.append(np.asarray(gap[served]))
        margins.append(np.asarray(margin[served]))
        if control:
            lows.append(np.asarray(low[served]))
    del w
    gap, margin = np.concatenate(gaps), np.concatenate(margins)
    keep = margin >= tie
    worst = np.argsort(-gap)[:5]
    log("widest raw gaps (gap, router tie margin): "
        + ", ".join(f"({gap[i]:.4f}, {margin[i]:.4f})" for i in worst))
    low = np.concatenate(lows) if control else None
    for t in (0.0, 0.01, 0.02, 0.05, 0.1, 0.2):
        k = margin >= t
        log(f"tie margin >= {t}: {int(k.sum())} tokens, widest gap "
            f"{gap[k].max(initial=0.0):.4f}"
            + (f", control {low[k].max(initial=0.0):.4f}" if control
               else ""))
    out = {"logit_gap": float(gap[keep].max(initial=0.0)),
           "tokens": int(gap.size), "near_ties": int((~keep).sum())}
    if control:
        out["logit_gap_control"] = float(low[keep].max(initial=0.0))
    return out


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def reduce_trace(trace_dir: Path, devices, keep: Optional[str]):
    from benchmarks.chip import trace
    iv = trace.load_xplane(trace.find_xplane(str(trace_dir)))
    if keep:
        iv.save(keep)
    red = trace.Reduced.for_span(iv, "bench.traced",
                                 devices=[d.id for d in devices])
    return red


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, platform: str = "tpu", control: bool = False,
             keep_trace: Optional[str] = None,
             t_process: float = T_PROCESS) -> dict:
    """Set up, drive, measure and check one cell; return the result line.
    ``platform`` is the JAX platform the run insists on."""
    import jax
    from benchmarks.chip.peaks import peaks_for

    cell = spec.cell(bench, cell_name)
    config = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    chips = int(cell["chips"])
    devices = jax.devices()
    if devices[0].platform != platform:
        raise BenchError(f"no {platform}: JAX's first device is "
                         f"{devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    devices = devices[:chips]
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if platform == "tpu" else None
    ref = spec.reference(config)
    dims = ref.dims_of(config)
    log(f"cache: {enable_compile_cache(jax)}")
    counter = CompileCounter(jax)

    ta = time.perf_counter()
    eng = build_engine(cell, config, seed)
    check_program_config(eng.cfg, ref, dims)
    add_spans(eng)
    clients = int(cell["serving"]["max_batch"])
    traffic = generator.Traffic(mix, seed, dims.vocab, clients)
    tb = time.perf_counter()
    warm_up(eng, traffic, seed)
    tc = time.perf_counter()
    loop = Loop(eng, traffic)
    if not traffic.open_loop:
        for _ in range(clients):
            loop.send(time.perf_counter())
        loop.step()                                  # admits the first wave
    t0 = time.perf_counter()
    if traffic.open_loop:
        loop.schedule = [t0 + t for t in traffic.send_times(seconds)]
    setup_s = t0 - t_process
    log(f"setup: {setup_s:.3f} s (start-up {ta - t_process:.3f}, engine "
        f"{tb - ta:.3f}, warm-up {tc - tb:.3f}, first wave of {clients} "
        f"{t0 - tc:.3f})")

    counter.armed = True
    traced, tr_lo, tr_hi = None, 0.3 * seconds, 0.3 * seconds + min(
        5.0, 0.3 * seconds)
    n_window_steps0 = len(loop.steps)
    while True:
        now = time.perf_counter()
        if trace and traced is None and now - t0 >= tr_lo:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            traced = jax.profiler.TraceAnnotation("bench.traced")
            traced.__enter__()
            i_trace = len(loop.steps)
        rec = loop.step()
        if traced is not None and not isinstance(traced, tuple) \
                and rec.t1 - t0 >= tr_hi:
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced = (i_trace, len(loop.steps))
        if rec.t1 - t0 >= seconds:
            break
    t1 = loop.steps[-1].t1
    counter.armed = False
    window_steps = loop.steps[n_window_steps0:]
    log(f"window: {t1 - t0:.3f} s, {len(window_steps)} steps, "
        f"{len(loop.done)} requests finished, {loop.sent} sent")
    log(f"programs built in the window: {counter.compiles}, of which "
        f"{counter.loads} loaded from the persistent cache and "
        f"{counter.compiles - counter.loads} compiled by XLA")

    peak = memory_peak(devices)
    log(f"memory peak per chip: {[memory_peak([d]) for d in devices]} "
        f"bytes of {[(d.memory_stats() or {}).get('bytes_limit') for d in devices]}")
    result_metrics, breakdown, dev_extra = {}, None, {}
    if not trace:
        wanted = spec.end_to_end(bench, cell_name)
        vals = end_to_end(loop, t0, t1, chips, [m["name"] for m in wanted])
        vals["setup_s"] = setup_s
        for m in wanted:
            result_metrics[m["name"]] = {"value": vals[m["name"]],
                                         "unit": m["unit"]}
    else:
        if not isinstance(traced, tuple):
            raise BenchError("the window ended before the traced steps did")
        red = reduce_trace(TRACE_DIR, devices, keep_trace)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        record = {"dims": dims, "peaks": peaks, "chips": chips,
               "max_batch": clients, "window_steps": window_steps,
               "traced_steps": loop.steps[traced[0]:traced[1]],
               "devices": [d.id for d in devices],
               "runtime": getattr(eng, "runtime", None)}
        for m in spec.per_layer(bench, cell_name):
            v = spec.reader(m["name"])(record, red)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev_extra = {"busy_s": red.mean_busy_s(), "window_s": red.window_s}
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.idle_gaps(devices[0].id, 10)}

    chk = cell["check"]
    sample = [(list(s.req.prompt), list(s.req.generated))
              for s in pick_sample(loop.done + loop.live, seed,
                                   chk["sample_tokens"], chk["max_requests"])]
    attempted = loop.sent
    # free the program before the reference runs
    del eng, loop, window_steps
    record = red = None
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"device bytes still live after freeing the program: {live}")
    limit = chk["limit"]["logit_gap"]
    tr = time.perf_counter()
    got = compare(ref, dims, seed, sample, int(cell["serving"]["max_seq"]),
                  chk["router_tie"], control, config["program"]["dtype"])
    log(f"reference: {time.perf_counter() - tr:.3f} s for {got['tokens']} "
        f"served tokens of {len(sample)} requests, {got['near_ties']} of "
        f"them within {chk['router_tie']} of a router tie")
    correct = bool(sample) and got["logit_gap"] <= limit
    checks = {"logit_gap": {"value": got["logit_gap"], "limit": limit,
                            "requests": len(sample),
                            "tokens": got["tokens"],
                            "near_ties": got["near_ties"]}}
    if control:
        checks["logit_gap_control"] = {"value": got["logit_gap_control"],
                                       "limit": limit}
    out = {"correct": correct, "attempted": attempted, "failed": 0,
           "metrics": result_metrics,
           "device": {"platform": devices[0].platform, "kind": kind,
                      "count": chips, "memory_peak_bytes": peak,
                      **dev_extra}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the float8 control's gap (calibration "
                         "of the correctness limit; not part of a run)")
    ap.add_argument("--keep-trace", default=None,
                    help="write the reduced trace intervals to this file")
    args = ap.parse_args(argv)
    try:
        bench = spec.load_benchmark()
        out = run_cell(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace), control=bool(args.control),
                       keep_trace=args.keep_trace)
    except BenchError as e:
        log(f"benchmark: FAIL: {e}")
        return 1
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
