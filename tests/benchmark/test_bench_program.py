"""The program's spans, counters and named scopes as the benchmark reads
them (``benchmarks/chip/program.py`` and the five readers built on it): on
hand-made intervals with known answers, on the committed chip traces, and
in a traced toy run on the CPU."""
import sys
import time
from pathlib import Path

import pytest

from benchmarks.chip import program, spec, trace, trace_program
from benchmarks.chip.program import ProgramIntervals, ProgramReduced, ScopedOp
from benchmarks.chip.run import StepRecord
from benchmarks.chip.trace import Intervals, Reduced, Span
from test_bench_run import cache_dir, toy  # noqa: F401 (fixtures)

TESTDATA = Path(__file__).resolve().parents[2] / "benchmarks" / "chip" / \
    "testdata"
NEW = ("prepare_ms", "sample_ms", "decode_call_ms", "host_syncs_per_step",
       "programs_built_per_step")


def _toy():
    """Window [0, 10] s.  Device 0 busy 1-2 (attention), 2-3 (experts,
    under a stage scope too) and 6-7 (no scope).  The engine steps from 0.5
    to 9: prepares 0.5-2.5, decodes 2.5-6.5, samples 6.5-8.5; outside any
    program span 0-0.5 and 9-10."""
    ops = {0: [ScopedOp("fusion.1", 1.0, 2.0,
                        "jit(f)/while/body/attention/dot"),
               ScopedOp("fusion.2", 2.0, 3.0,
                        "jit(f)/expert/experts/dot_general"),
               ScopedOp("copy.3", 6.0, 7.0, "")]}
    spans = [Span("bench.traced", 0.0, 10.0), Span("bench.step", 0.0, 9.5)]
    prog = [Span("engine.step", 0.5, 9.0), Span("engine.prepare", 0.5, 2.5),
            Span("engine.decode", 2.5, 6.5), Span("engine.sample", 6.5, 8.5)]
    return ProgramIntervals(ops, spans, prog)


def test_program_span_time_and_scopes():
    red = ProgramReduced.for_span(_toy(), "bench.traced")
    assert red.program_span_time_s("engine.decode") == pytest.approx(4.0)
    assert red.program_span_time_s("engine.step") == pytest.approx(8.5)
    assert red.program_span_time_s("bench.step") == 0.0
    assert dict(red.device_time_by_scope()) == pytest.approx(
        {"attention": 1.0, "experts": 1.0, "unscoped": 1.0})
    assert program.scope_label("jit(s)/attn/router/gather") == "router"
    assert program.scope_label("jit(s)/while/body/add") == "unscoped"


def test_idle_time_goes_to_the_innermost_program_span():
    red = ProgramReduced.for_span(_toy(), "bench.traced")
    idle = dict(red.idle_by_program_span(0))
    # outside 0-0.5 and 9-10; prepare 0.5-1; decode 3-6 (the copy runs
    # 6-7, across the decode's end); sample 7-8.5; the step alone 8.5-9
    assert idle == pytest.approx({
        program.OUTSIDE: 1.5, "engine.prepare": 0.5, "engine.decode": 3.0,
        "engine.sample": 1.5, program.STEP_SELF: 0.5})
    assert sum(idle.values()) == pytest.approx(
        red.window_s - red.busy_s(0))


def test_intervals_round_trip_with_and_without_program_spans(tmp_path):
    iv = _toy()
    iv.save(str(tmp_path / "p.json.gz"))
    back = ProgramIntervals.load(str(tmp_path / "p.json.gz"))
    assert back.program_spans == iv.program_spans
    assert back.ops[0][1].scope == "jit(f)/expert/experts/dot_general"
    plain = Intervals(ops={0: [trace.Op("fusion.1", 1.0, 2.0)]},
                      spans=[Span("bench.traced", 0.0, 3.0)])
    plain.save(str(tmp_path / "q.json.gz"))
    old = ProgramIntervals.load(str(tmp_path / "q.json.gz"))
    assert old.program_spans == [] and old.ops[0][0].scope == ""


def test_the_committed_sample_reads_as_before():
    """The first recorded trace, loaded as program intervals, gives the
    existing readers and the idle gaps the values it always gave."""
    path = str(TESTDATA / "trace_sample.json.gz")
    red = Reduced.for_span(Intervals.load(path), "bench.traced")
    prg = ProgramReduced.for_span(ProgramIntervals.load(path),
                                  "bench.traced")
    assert prg.iv.program_spans == []
    assert prg.busy_s(0) == red.busy_s(0)
    assert prg.idle_share() == red.idle_share()
    assert prg.op_time_s("decode_attention") == \
        red.op_time_s("decode_attention")
    assert prg.top_ops(5) == red.top_ops(5)
    assert prg.idle_gaps(0) == red.idle_gaps(0)
    assert prg.span_time_s("bench.admit") == red.span_time_s("bench.admit")
    for name in ("idle_share", "admit_share"):
        assert spec.reader(name)({}, prg) == spec.reader(name)({}, red)
    # no program span: all idle time lies outside the engine
    idle = dict(prg.idle_by_program_span(0))
    assert list(idle) == [program.OUTSIDE]
    assert idle[program.OUTSIDE] == pytest.approx(
        prg.window_s - prg.busy_s(0))


def _rec(n_steps=4):
    return {"traced_steps": [StepRecord(10.0 + i, 11.0 + i, 8, [], [])
                             for i in range(n_steps)]}


def test_readers_divide_by_the_decode_steps(monkeypatch):
    from repro import obs
    seen = []

    def window(t0, t1):
        seen.append((t0, t1))
        return ({"engine.prepare": (4, 0.8), "engine.sample": (4, 1.6),
                 "engine.decode": (4, 0.6)},
                {"decode_steps": 4, "host_syncs": 1026,
                 "programs_built": 5})
    monkeypatch.setattr(obs, "window", window)
    got = {m: spec.reader(m)(_rec(), None) for m in NEW}
    assert got == pytest.approx({
        "prepare_ms": 200.0, "sample_ms": 400.0, "decode_call_ms": 150.0,
        "host_syncs_per_step": 256.5, "programs_built_per_step": 1.25})
    assert seen[0] == (10.0, 14.0)


def test_readers_are_silent_without_the_program_record(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "window", lambda t0, t1: ({}, {}))
    assert all(spec.reader(m)(_rec(), None) is None for m in NEW)
    monkeypatch.setattr(obs, "window", lambda t0, t1: (
        {"engine.decode": (2, 0.1)}, {"decode_steps": 2}))
    got = {m: spec.reader(m)(_rec(), None) for m in NEW}
    assert got["prepare_ms"] is None and got["decode_call_ms"] == 50.0
    assert got["host_syncs_per_step"] == 0.0
    assert all(spec.reader(m)({"traced_steps": []}, None) is None
               for m in NEW)
    # a program older than repro.obs
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert all(spec.reader(m)(_rec(), None) is None for m in NEW)


def test_traced_toy_run_reads_the_program(toy):
    """A traced run of the toy cell on the CPU: the five metrics are read,
    each per-step count is the engine's own counter over the traced decode
    steps, and the profiler trace holds the engine's spans on the window's
    clock."""
    seen = {}
    reader = spec.reader

    def capturing(name):
        fn = reader(name)

        def read(rec, red):
            seen.setdefault("steps", list(rec["traced_steps"]))
            return fn(rec, red)
        return read
    toy.setattr(spec, "reader", capturing)
    out = trace_program.traced(spec.load_benchmark(), "toy.decode", 29, 3.0,
                               platform="cpu", t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert all(m[k] is not None and m[k] >= 0 for k in NEW)
    assert m["prepare_ms"] > 0 and m["sample_ms"] > 0 \
        and m["decode_call_ms"] > 0
    steps = seen["steps"]
    c = out["counters"]
    assert c["decode_steps"] == len(steps)
    assert m["host_syncs_per_step"] == pytest.approx(
        c["host_syncs"] / len(steps))
    # at least one read a step, and no more than one a token the client
    # saw: a batched read passes, a counter that stopped counting does not
    tokens = sum(len(s.prefill) + len(s.decode_ctx) for s in steps)
    assert 1 <= m["host_syncs_per_step"] <= tokens / len(steps)
    assert m["programs_built_per_step"] == pytest.approx(
        c.get("programs_built", 0) / len(steps))
    idle = dict(out["breakdown"]["idle_by_span"])
    assert "engine.decode" in idle and "engine.sample" in idle
    assert set(idle) <= {program.OUTSIDE, program.STEP_SELF,
                         "engine.retire", "engine.admit", "engine.prefill",
                         "engine.insert", "engine.prepare", "engine.decode",
                         "engine.sample"}
    per = out["per_step_ms"]
    inside = sum(per[k] for k in ("engine.retire", "engine.admit",
                                  "engine.prepare", "engine.decode",
                                  "engine.sample"))
    assert inside <= per["engine.step"] <= per["client_step"]


def test_recorded_program_spans_share_the_device_clock():
    """One engine step of ``mixtral.decode`` traced on a TPU v5e: every
    program span lies inside a harness step span (both are host events of
    one trace, on the clock of the device operations), the engine's
    spans take the device's idle time, and the named scopes reach the
    device operations."""
    iv = ProgramIntervals.load(str(TESTDATA / "trace_sample_program.json.gz"))
    red = ProgramReduced.for_span(iv, "bench.traced")
    steps = [s for s in iv.spans if s.name == "bench.step"]
    names = {s.name for s in iv.program_spans}
    assert {"engine.step", "engine.prepare", "engine.decode",
            "engine.sample"} <= names
    for p in iv.program_spans:
        assert any(b.start <= p.start and p.end <= b.end for b in steps), p
    scopes = dict(red.device_time_by_scope())
    assert scopes["experts"] > 0 and scopes["attention"] > 0
    idle = dict(red.idle_by_program_span(0))
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s(0))
    inside = sum(v for k, v in idle.items()
                 if k not in (program.STEP_SELF, program.OUTSIDE))
    assert inside > 0.9 * sum(idle.values())


def test_named_scopes_and_program_spans_from_a_profiler_trace(tmp_path):
    """A trace written with the programs' HLO: each instruction's
    named-scope path is read from it, and the program's spans are kept
    beside the harness's."""
    import jax
    import jax.numpy as jnp
    from repro import obs

    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            y = jnp.sin(x) @ x
        with jax.named_scope("experts"):
            return jnp.cos(y) @ y
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.enable_hlo_proto = True
    opts.python_tracer_level = 0
    rec = obs.Recorder()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        with rec.span("engine.step", step=0):
            with rec.span("engine.decode"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    scopes = program.module_scopes(path)
    paths = [p for name, m in scopes.items() if name.startswith("jit_f(")
             for p in m.values()]
    assert {program.scope_label(p) for p in paths} >= {"attention",
                                                       "experts"}
    iv = program.load_xplane(path)
    assert [s.name for s in iv.program_spans] == ["engine.step",
                                                  "engine.decode"]
    assert [s.name for s in iv.spans] == ["bench.traced"]
    outer, inner = iv.program_spans
    assert outer.start <= inner.start < inner.end <= outer.end
