"""The whole run of a cell, on the CPU at a toy size of the same model
family: the harness drives the engine, and the float32 reference decides
``correct``.  The control (the reference's float8 pass in the program's
place) and each fault planted in the timed path must come out not
correct; a sound run must come out correct.

The toy model is the launcher's reduced mixtral (``use_reduced``): d 256,
4 query / 1 kv heads of 64, 4 experts of 128, top-2, vocabulary 512, two
layers.  Its limit is set for this size from CPU readings: sound bfloat16
runs read about 0.01, the float8 control about 0.7.  The check reads
every served request: a toy window holds few, and a fault that spares
half of the batch's rows must meet a sampled request in the other half.
"""
import time
import types

import numpy as np
import pytest

from benchmarks.chip import peaks, run, spec
from benchmarks.chip.references import moe_gqa

TOY = {"num_hidden_layers": 2, "hidden_size": 256, "intermediate_size": 128,
       "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 64,
       "num_local_experts": 4, "num_experts_per_tok": 2, "vocab_size": 512,
       "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "reference": "moe_gqa",
       "program": {"arch": "mixtral-8x22b", "use_reduced": True,
                   "n_layers": 0, "dtype": "bfloat16"}}
MIX = {"block": 16, "blocks": 4, "prompt_classes": {"8": 0.5, "16": 0.5},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                  "min": 4, "max": 24}}
TOY_LIMIT = 0.1
CELL = {"name": "toy.decode", "config": "toy", "traffic": "toy", "chips": 1,
        "serving": {"runtime": "monolithic", "use_kernels": True,
                    "max_batch": 8, "max_seq": 64},
        "check": {"sample_tokens": 10_000, "max_requests": 64,
                  "router_tie": 0.0, "limit": {"logit_gap": TOY_LIMIT}}}


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """A persistent compile cache of this module's own: the engine builds
    its layer scan anew at every eager call and finds it there."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    yield str(tmp_path_factory.mktemp("jax_cache"))
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture
def toy(monkeypatch, cache_dir):
    monkeypatch.setattr(spec, "cell", lambda bench, name: CELL)
    monkeypatch.setattr(spec, "config", lambda bench, name: TOY)
    monkeypatch.setattr(spec, "traffic", lambda name: MIX)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    return monkeypatch


def _run(seed=2**31 + 5, trace=False, control=False):
    return run.run_cell(spec.load_benchmark(), "toy.decode", seed, 3.0,
                        trace, platform="cpu", control=control,
                        t_process=time.perf_counter())


def _with_fault(monkeypatch, fault):
    build = run.build_engine

    def broken(*a, **k):
        eng = build(*a, **k)
        fault(eng)
        return eng
    monkeypatch.setattr(run, "build_engine", broken)


def test_sound_run_is_correct_and_the_control_is_not(toy):
    out = _run(control=True)
    assert out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] <= TOY_LIMIT
    assert out["checks"]["logit_gap"]["tokens"] > 0
    assert out["checks"]["logit_gap_control"]["value"] > TOY_LIMIT
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert set(m) == {"tok_s_per_chip", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert out["device"]["count"] == 1 and out["attempted"] > 8


def _token_altered(eng):
    import repro.serving.engine as engine_mod
    sample_rows = engine_mod.sample_rows

    def altered(logits, *a, **k):
        return (sample_rows(logits, *a, **k) + 1) % logits.shape[-1]
    eng._restore = sample_rows
    engine_mod.sample_rows = altered


def _state_unchanged(eng):
    decode = eng._decode
    eng._decode = lambda toks, cache, pos: (decode(toks, cache, pos)[0],
                                            cache)


def _half_batch_left_out(eng):
    decode = eng._decode

    def half(toks, cache, pos):
        logits, cache = decode(toks, cache, pos)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), cache
    eng._decode = half


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch_left_out])
def test_a_broken_timed_path_is_not_correct(toy, fault):
    import repro.serving.engine as engine_mod
    toy.setattr(engine_mod, "sample_rows", engine_mod.sample_rows)
    _with_fault(toy, fault)
    out = _run(seed=17)
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > TOY_LIMIT


def test_no_tpu_no_result(toy):
    with pytest.raises(run.BenchError, match="no tpu"):
        run.run_cell(spec.load_benchmark(), "toy.decode", 1, 1.0, False)


def test_pick_sample_holds_the_longest():
    class R:
        def __init__(self, rid, n):
            self.rid, self.generated = rid, [0] * n
    done = [run.Sent(R(i, n), 0.0) for i, n in enumerate([3, 40, 5, 7, 9])]
    a = run.pick_sample(done, 5, want_tokens=50, max_requests=3)
    b = run.pick_sample(done, 5, want_tokens=50, max_requests=3)
    assert a[0].req.rid == 1 and [s.req.rid for s in a] == [
        s.req.rid for s in b]
    assert 2 <= len(a) <= 3
    assert run.pick_sample([], 5, 10, 3) == []


def test_percentile_matches_statistics():
    xs = list(np.linspace(0.0, 1.0, 101))
    # exclusive quantiles: 102 * 0.95 = 96.9 -> x[95] + 0.9 * (x[96] - x[95])
    assert run.p95(xs) == pytest.approx(0.959)


def test_open_loop_cell_reports_time_to_first_token(toy):
    """A cell whose mix is open loop starts empty and sends at the mix's
    seeded times; a metric entry that lists it gets ``ttft_p95_ms``."""
    toy.setattr(spec, "traffic",
                lambda name: dict(MIX, loop="open", rate_per_s=6.0))
    bench = spec.load_benchmark()
    bench["end_to_end"].append({"name": "ttft_p95_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["toy.decode"]})
    out = run.run_cell(bench, "toy.decode", 23, 3.0, False, platform="cpu",
                       t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["metrics"]["ttft_p95_ms"]["value"] > 0
    assert 5 <= out["attempted"] <= 40


def _recording(seen: list):
    """A reference module of a family of its own: ``moe_gqa``'s
    arithmetic, with every call into it noted in ``seen``."""
    class Dims(moe_gqa.Dims):
        def decode_token_flops(self, ctx):
            seen.append("decode_token_flops")
            return super().decode_token_flops(ctx)

        def prefill_flops(self, prompt_len):
            seen.append("prefill_flops")
            return super().prefill_flops(prompt_len)

    def noted(name, fn):
        def call(*a, **k):
            seen.append(name)
            return fn(*a, **k)
        return call

    def dims_of(config):
        seen.append("dims_of")
        return Dims(**vars(moe_gqa.dims_of(config)))

    return types.SimpleNamespace(
        Dims=Dims, dims_of=dims_of,
        forward=noted("forward", moe_gqa.forward),
        program_sizes=noted("program_sizes", moe_gqa.program_sizes),
        sizes=noted("sizes", moe_gqa.sizes),
        init_weights=noted("init_weights", moe_gqa.init_weights))


def test_the_run_takes_the_model_from_the_named_reference(toy):
    """A configuration that names another reference module gets every
    model-shaped part of a run from it: the sizes, the program check, the
    weights, the served tokens' gaps and the work the ``mfu`` reader
    counts.  So a family of another shape needs new files only."""
    seen, records = [], []
    module = _recording(seen)
    load = spec.reference
    toy.setattr(spec, "config",
                lambda bench, name: dict(TOY, reference="recording"))
    toy.setattr(spec, "reference", lambda config: module
                if config["reference"] == "recording" else load(config))
    reader = spec.reader

    def with_peaks(name):
        # the CPU has no peaks; the counts are what this test reads
        fn = reader(name)

        def read(rec, red):
            records.append((rec, red))
            return fn({**rec, "peaks": peaks.peaks_for("TPU v5 lite")}, red)
        return read
    toy.setattr(spec, "reader", with_peaks)
    out = _run(trace=True)
    assert out["correct"], out["checks"]
    assert all(type(rec["dims"]) is module.Dims for rec, _ in records)
    for name in ("dims_of", "program_sizes", "sizes", "init_weights",
                 "forward", "decode_token_flops"):
        assert name in seen, name
    rec, red = records[0]
    d = moe_gqa.dims_of(TOY)
    work = sum(d.prefill_flops(P) + 0.0 for s in rec["traced_steps"]
               for P in s.prefill) + sum(
        d.decode_token_flops(c) for s in rec["traced_steps"]
        for c in s.decode_ctx)
    assert work > 0
    assert out["metrics"]["mfu"]["value"] == pytest.approx(
        100.0 * work / (red.window_s * 197e12))


@pytest.mark.parametrize("name", ["mixtral", "../reference"])
def test_an_unknown_reference_lists_the_modules_present(name):
    with pytest.raises(run.BenchError) as err:
        spec.reference({"reference": name})
    named, listed = str(err.value).split("references/ holds ")
    assert repr(name) in named
    present = [p.stem for p in (spec.HERE / "references").glob("*.py")
               if not p.stem.startswith("_")]
    assert "moe_gqa" in present
    assert all(repr(m) in listed for m in present)


def test_the_program_check_compares_the_modules_two_readings():
    ref = types.SimpleNamespace(program_sizes=lambda cfg: {"a": 1, "b": 2},
                                sizes=lambda d: {"a": 1, "b": 3})
    with pytest.raises(run.BenchError, match=r"'b': \(2, 3\)"):
        run.check_program_config(None, ref, None)
    ref.sizes = lambda d: {"a": 1, "b": 2}
    run.check_program_config(None, ref, None)
