"""The DeepSeek-V3 configuration and its reference module
(``references/mla_moe.py``): the file against the program's cut, the
counts at published widths, the weight draw and the forward pass against
the program at a toy size on the CPU, a toy cell through the whole
harness, the ``expert_rows`` counter, and the ``mixtral.steady`` cell's
files.

The toy model is the launcher's reduced DeepSeek-V3 (``use_reduced``):
d 256, 4 heads, q and kv ranks 64, 32 + 16 query/key and 32 value
dimensions a head, one dense layer of 1024 and two MoE layers whose
router has 16 experts in 4 groups (2 kept, top-4), 4 of them held, one
shared expert of 128, vocabulary 512.  Its limit and tie margin are set
for this size from CPU readings: sound bfloat16 runs read about 0.003
away from held-expert ties, ties within 0.002 about 0.17, the float8
control 0.7 to 1.1.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import generator, reference, run, spec
from benchmarks.chip.references import mla_moe

BENCH = spec.load_benchmark()
FILE = spec.config(BENCH, "deepseek-v3")
TOY = dict(FILE, hidden_size=256, num_attention_heads=4,
           num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=64,
           qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
           num_hidden_layers=3, first_k_dense_replace=1,
           intermediate_size=1024, n_routed_experts=4,
           num_experts_per_tok=4, n_group=4, topk_group=2,
           moe_intermediate_size=128, vocab_size=512,
           expert_share={"router_experts": 16, "first_held": 0,
                         "chips_per_layer": 4},
           program={"arch": "deepseek-v3", "use_reduced": True,
                    "n_layers": 0, "experts_held": 4, "dtype": "bfloat16"})
D = mla_moe.dims_of(TOY)
MIX = {"block": 16, "blocks": 4, "prompt_classes": {"8": 0.5, "16": 0.5},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                  "min": 4, "max": 24}}
TOY_LIMIT = 0.1
CELL = {"name": "toy.decode", "config": "toy", "traffic": "toy", "chips": 1,
        "serving": {"runtime": "monolithic", "use_kernels": True,
                    "max_batch": 8, "max_seq": 64},
        "check": {"sample_tokens": 10_000, "max_requests": 64,
                  "router_tie": 0.01, "limit": {"logit_gap": TOY_LIMIT}}}


def _cut(config):
    """The launcher's model for a configuration file's ``program``."""
    from repro.config import chip_share, get_config, reduced
    p = config["program"]
    cfg = get_config(p["arch"])
    if p["use_reduced"]:
        cfg = reduced(cfg)
    return chip_share(cfg, n_layers=p["n_layers"],
                      lead_layers=p.get("lead_layers", 0),
                      experts_held=p["experts_held"],
                      vocab=p.get("vocab", 0))


def _program(seed, dtype):
    """The toy model and the launcher's weights for it."""
    from repro.models import init_params
    cfg = _cut(TOY)
    return cfg, jax.jit(init_params, static_argnums=(0, 2))(
        cfg, jax.random.PRNGKey(seed), dtype)


def test_the_file_keeps_the_published_widths_and_the_program_serves_it():
    d = spec.reference(FILE).dims_of(FILE)
    assert spec.reference(FILE) is mla_moe
    assert (d.d_model, d.n_heads, d.q_lora_rank, d.kv_lora_rank,
            d.qk_nope_head_dim, d.qk_rope_head_dim, d.v_head_dim,
            d.d_ff_dense, d.d_ff_expert, d.n_experts, d.top_k, d.n_group,
            d.topk_group, d.routed_scaling) == (
        7168, 128, 1536, 512, 128, 64, 128, 18432, 2048, 256, 8, 8, 4, 2.5)
    # the cut: depth, the leading dense layers, the experts held, the vocab
    assert (d.n_layers, d.n_dense_layers, d.n_held, d.vocab) == (
        5, 1, 8, 16160)
    reduced = {c["name"]: c["reduced"] for c in BENCH["configs"]}
    assert sorted(reduced["deepseek-v3"]) == sorted(FILE["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert {k: v[0] for k, v in FILE["reduced"].items()} == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280}
    cfg = _cut(FILE)
    run.check_program_config(cfg, mla_moe, d)
    assert mla_moe.program_sizes(cfg) == mla_moe.sizes(d)


def test_counts_at_published_widths():
    d = mla_moe.dims_of(FILE)
    # W_qa 7168*1536 + W_qb 1536*128*192 + W_kva 7168*576
    # + W_kvb 512*128*256 + W_o 128*128*7168
    assert d.attn_params() == 187_105_280
    # 5 attentions; 4 x (router 7168*256 + shared 44,040,192 + a quarter
    # of a routed expert); one dense FFN of 3*7168*18432
    assert d.token_matmul_params() == (
        5 * 187_105_280 + 4 * (1_835_008 + 44_040_192 + 11_010_048)
        + 396_361_728) == 1_559_429_120
    # 2 * (1,559,429,120 + 7168*16160) + 2*128*(576+512) * 1000 * 5
    assert d.decode_attention_flops() == 278_528
    assert d.decode_token_flops(1000) == 4_743_168_000
    # 2*128*1,559,429,120 + 2*128*(192+128) * (128*129/2) * 5 + 2*7168*16160
    assert d.prefill_flops(128) == 402_827_182_080
    f, b = d.decode_attention_cost([100, 300])
    assert f == 278_528 * 400
    # q (128x576) and out (128x512) of 2 rows, 1152 bytes a held position
    assert b == 2 * 128 * (576 + 512) * 2 + 400 * 1152 == 1_017_856


def test_yarn_scale_and_frequencies_match_the_programs():
    from repro.config import get_config
    from repro.models.common import yarn_frequencies
    from repro.models.mla import softmax_scale
    d = mla_moe.dims_of(FILE)
    cfg = get_config("deepseek-v3")
    assert mla_moe.softmax_scale(d) == pytest.approx(
        192 ** -0.5 * 1.3688879454113936 ** 2, rel=1e-12)
    assert mla_moe.softmax_scale(d) == softmax_scale(cfg)
    np.testing.assert_array_equal(
        mla_moe.rope_frequencies(d),
        yarn_frequencies(64, cfg.rope_theta, cfg.rope_scaling))


def test_weights_are_the_launchers_draw():
    seed = 2**31 + 11
    _, p = _program(seed, jnp.bfloat16)
    w = mla_moe.init_weights(D, seed, jnp.bfloat16)
    for k in ("embed", "lm_head", "final_norm"):
        np.testing.assert_array_equal(np.asarray(w[k]), np.asarray(p[k]))
    for k, v in w["dense"][0].items():
        assert v.dtype == p["lead"][0][k].dtype, k
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(p["lead"][0][k]), err_msg=k)
    assert set(w["moe"]) == set(p["blocks"][0])
    for k, v in w["moe"].items():
        assert v.dtype == p["blocks"][0][k].dtype, k
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(p["blocks"][0][k]),
                                      err_msg=k)


def test_reference_matches_prefill_and_decode_through_the_latent_cache():
    """At float32 on the CPU the program's prefill logits, and its decode
    logits through the latent cache, agree with the reference's full
    forward pass at every position to rounding."""
    from repro.models import decode_step, prefill
    seed = 7
    cfg, p = _program(seed, jnp.float32)
    w = mla_moe.init_weights(D, seed, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 512, 24).astype(np.int32)
    ref = np.asarray(mla_moe.forward(w, D, jnp.asarray(toks))[0])
    with jax.default_matmul_precision("highest"):
        last, cache = prefill(p, cfg, jnp.asarray(toks[None, :9]), max_seq=32)
        np.testing.assert_allclose(np.asarray(last)[0], ref[8], atol=2e-4)
        for t in range(9, 24):
            logits, cache = decode_step(p, cfg, jnp.asarray(toks[t:t + 1]),
                                        cache, jnp.asarray([t], jnp.int32),
                                        use_kernels=True)
            np.testing.assert_allclose(np.asarray(logits)[0], ref[t],
                                       atol=2e-4, err_msg=str(t))


def test_tie_margin_is_the_held_experts_distance_from_a_flip():
    """The margin is small exactly where a perturbation of the selection
    scores can change which held experts a token gets."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(256, 16)), jnp.float32)
    bias = jnp.zeros((16,))
    gate, margin = mla_moe.route(logits, bias, D)
    assert gate.shape == (256, 4) and np.all(np.asarray(margin) >= 0)
    base = np.asarray(gate) > 0
    eps = np.asarray(margin)
    for t in np.argsort(eps)[:8].tolist() + np.argsort(-eps)[:8].tolist():
        flips = 0
        for e in range(16):
            for sign in (1.0, -1.0):
                # move one expert's logit by more than the margin allows
                bumped = logits.at[t, e].add(sign * 40 * eps[t] + 1e-6)
                g, _ = mla_moe.route(bumped, bias, D)
                flips += np.any((np.asarray(g)[t] > 0) != base[t])
        small = eps[t] < 0.02
        assert flips > 0 or not small, (t, eps[t])
    # far from a flip: shifting every score by less than half the margin
    # changes no held choice
    far = np.asarray(margin) > 0.05
    g, _ = mla_moe.route(logits + 0.01 * jnp.asarray(
        rng.normal(size=(256, 16)), jnp.float32), bias, D)
    assert far.any()
    np.testing.assert_array_equal((np.asarray(g) > 0)[far], base[far])


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
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


def test_a_toy_cell_is_correct_and_its_control_is_not(toy):
    """The whole harness on the toy: the engine serves it through the
    latent cache and the held experts, the reference decides ``correct``,
    and the float8 control is over the limit.  Traced, the
    ``expert_rows`` reader gives the rows the routed-expert matmuls
    computed: 4 held experts x capacity 8 (the batch) x 2 MoE layers."""
    bench = spec.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] == "expert_rows_per_step":
            m["workloads"] = ["toy.decode"]
    out = run.run_cell(bench, "toy.decode", 2**31 + 5, 3.0, True,
                       platform="cpu", control=True,
                       t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    chk = out["checks"]
    assert chk["logit_gap"]["value"] <= TOY_LIMIT
    assert chk["logit_gap"]["tokens"] > 20
    assert chk["logit_gap_control"]["value"] > TOY_LIMIT
    assert out["metrics"]["expert_rows_per_step"]["value"] == 4 * 8 * 2


def test_expert_rows_counts_what_the_dispatch_computed(toy):
    """One decode step of the toy engine: the counter rises by the rows of
    the dispatch buffers the routed experts' matmuls ran over, once per
    MoE layer."""
    from repro.models import moe as moe_lib
    eng = run.build_engine(CELL, TOY, 3)
    from repro.serving.engine import Request
    for i in range(3):
        eng.submit(Request(rid=i, prompt=list(range(1, 9)),
                           max_new_tokens=4))
    eng.step()                                  # admits and decodes once
    shapes, dispatch = [], moe_lib.dispatch_indices

    def recording(*a, **k):
        idx, gate = dispatch(*a, **k)
        shapes.append(idx.shape)
        return idx, gate
    toy.setattr(moe_lib, "dispatch_indices", recording)
    before = eng.obs.counters().get("expert_rows", 0)
    eng.step()
    rows = eng.obs.counters()["expert_rows"] - before
    # the decode traces the scanned MoE layer once and runs it n_blocks
    # times: (held experts, capacity = the batch of 8 rows)
    assert shapes == [(4, 8)]
    assert eng.cfg.n_moe_layers == eng.cfg.n_blocks == 2
    assert rows == 4 * 8 * 2


def test_mixtral_steady_cell_loads():
    steady = spec.traffic("decode_steady")
    heavy = spec.traffic("decode_heavy")
    assert steady == dict(heavy, start="steady")
    cell = spec.cell(BENCH, "mixtral.steady")
    assert cell["config"] == "mixtral-8x22b" and cell["chips"] == 1
    assert cell["serving"] == spec.cell(BENCH, "mixtral.decode")["serving"]
    assert cell["check"] == spec.cell(BENCH, "mixtral.decode")["check"]
    t = generator.Traffic(steady, 2**31 + 9, 32000, clients=256)
    assert t.steady_start
    first = [s.max_new for s in t.specs[:256]]
    # the residual lives of a mean output of ~257: about one finishes a step
    assert min(first) == 2 and 0.5 <= sum(x <= 3 for x in first) <= 4
    ds = spec.cell(BENCH, "deepseek.decode")
    assert (ds["config"], ds["traffic"], ds["chips"]) == (
        "deepseek-v3", "decode_heavy", 1)
    assert ds["serving"] == cell["serving"]


def test_served_gaps_are_zero_for_the_references_own_choices():
    w = mla_moe.init_weights(D, 5, jnp.float32)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 512, 16),
                       jnp.int32)
    ref = mla_moe.forward(w, D, toks)[0]
    best = jnp.argmax(ref, axis=-1).astype(jnp.int32)
    gap, low, margin = reference.served_gaps(mla_moe.forward, w, D, toks,
                                             best, True)
    assert float(jnp.max(gap)) == 0.0 and float(jnp.min(low)) >= 0.0
    assert margin.shape == (16,) and float(jnp.min(margin)) >= 0.0
