"""DeepSeek-V3's pieces at a small size on the CPU: multi-head latent
attention (the absorbed decode, its Pallas kernel in interpret mode, the
latent cache against the expanded prefill), group-limited sigmoid routing
against a brute-force selection, the share of held experts against the
uncut layer, the one-chip cut, and the runtimes that refuse what they do
not serve yet."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import chip_share, get_config, reduced
from repro.kernels import ops
from repro.models import decode_step, forward_train, init_params, prefill
from repro.models import moe as moe_lib
from repro.models.attention import attention, latent_decode_attention
from repro.models.common import yarn_frequencies, yarn_softmax_scale
from repro.models.ffn import gated_ffn


def _toy(held: int = 4):
    """Reduced DeepSeek-V3: one dense layer, two MoE layers of 16 routed
    experts in 4 groups (2 kept, top-4), ``held`` of them held."""
    return chip_share(reduced(get_config("deepseek-v3")), experts_held=held)


@pytest.fixture(scope="module")
def toy():
    cfg = _toy()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_the_one_chip_cut_keeps_every_width():
    full = get_config("deepseek-v3")
    cut = chip_share(full, n_layers=5, lead_layers=1, experts_held=8,
                     vocab=16160)
    assert cut.lead_pattern == ("mla_dense",) and cut.n_blocks == 4
    assert (cut.n_layers, cut.n_moe_layers, cut.vocab) == (5, 4, 16160)
    assert (cut.moe.n_experts, cut.moe.held, cut.moe.first_held,
            cut.moe.top_k) == (256, 8, 0, 8)
    assert (cut.d_model, cut.d_ff, cut.mla, cut.moe.d_ff_expert) == (
        full.d_model, full.d_ff, full.mla, full.moe.d_ff_expert)
    assert full.n_moe_layers == 58 and round(cut.param_count() / 1e6) == 3156
    with pytest.raises(ValueError, match="held"):
        dataclasses.replace(cut.moe, n_held=8, first_held=250)


def test_yarn_table_and_softmax_scale():
    y = get_config("deepseek-v3").rope_scaling
    f = yarn_frequencies(64, 1e4, y)
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    # fast dimensions keep the base frequency, slow ones are divided by 40
    np.testing.assert_allclose(f[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(f[-8:], base[-8:] / 40, rtol=1e-6)
    assert np.all(np.diff(f) < 0)
    assert yarn_softmax_scale(192, y) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_latent_cache_decode_matches_the_full_forward(toy, use_kernels):
    """Prefill fills the latent cache; the absorbed decode through it
    (jnp, or the Pallas kernel in interpret mode) gives the logits the
    expanded full-sequence forward gives."""
    cfg, params = toy
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        full, _ = forward_train(params, cfg, toks, remat="none",
                                capacity_mode="full")
        last, cache = prefill(params, cfg, toks, max_seq=32)
        np.testing.assert_allclose(last, full[:, -1], atol=2e-5)
        assert cache["lead"][0]["latent"].shape == (2, 32, 64 + 16)
        assert cache["blocks"][0]["latent"].shape == (2, 2, 32, 80)
        nxt = jnp.argmax(last, -1)
        full2, _ = forward_train(params, cfg, jnp.concatenate(
            [toks, nxt[:, None]], 1), remat="none", capacity_mode="full")
        logits, new = decode_step(params, cfg, nxt, cache,
                                  jnp.full((2,), 12, jnp.int32),
                                  use_kernels=use_kernels)
    np.testing.assert_allclose(logits, full2[:, -1], atol=2e-5)
    assert int(new["lead"][0]["pos"][0, 12]) == 12


@pytest.mark.parametrize("B,H,D,V,W,wb", [(2, 4, 80, 64, 32, 512),
                                          (3, 8, 96, 64, 256, 128),
                                          (2, 16, 576, 512, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_kernel_matches_the_oracle(B, H, D, V, W, wb, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * W + D), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    lat = jax.random.normal(ks[1], (B, W, D), jnp.float32).astype(dtype)
    pos = jax.random.randint(ks[2], (B,), 0, W)
    cpos = jnp.where(jnp.arange(W)[None] <= pos[:, None], jnp.arange(W)[None],
                     -1).astype(jnp.int32)
    got = ops.mla_decode_attention(q, lat, cpos, pos, v_dim=V, scale=0.07,
                                   wb=wb)
    want = latent_decode_attention(q, lat, cpos, pos, v_dim=V, scale=0.07)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_chunked_prefill_attention_keeps_the_value_width():
    """Expanded MLA attends with q . k over 192 dimensions and values of
    128: the query-chunked path returns the values' width."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, 10, 2, 24))
    k = jax.random.normal(ks[1], (1, 10, 2, 24))
    v = jax.random.normal(ks[2], (1, 10, 2, 16))
    pos = jnp.arange(10)[None]
    whole = attention(q, k, v, pos, pos)
    chunked = attention(q, k, v, pos, pos, q_chunk=4)
    assert chunked.shape == (1, 10, 2, 16)
    np.testing.assert_allclose(chunked, whole, atol=1e-6)


def _brute_force_routing(scores, bias, m):
    """One token at a time, in NumPy: the experts DeepSeek-V3 chooses and
    their weights, as {expert: weight}."""
    out = []
    per = m.n_experts // m.n_group
    for s, c in zip(scores, scores + bias):
        groups = [sorted(c[g * per:(g + 1) * per])[-2:] for g in
                  range(m.n_group)]
        kept = np.argsort([-sum(g) for g in groups], kind="stable")[
            :m.topk_group]
        cand = [e for e in range(m.n_experts) if e // per in kept]
        chosen = sorted(cand, key=lambda e: -c[e])[:m.top_k]
        total = sum(s[e] for e in chosen)
        out.append({e: s[e] / total * m.routed_scaling for e in chosen})
    return out


def test_group_limited_sigmoid_routing_matches_brute_force():
    m = get_config("deepseek-v3").moe
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (64, 32))
    w = jax.random.normal(ks[1], (32, m.n_experts)) * 0.3
    bias = 0.01 * jax.random.normal(ks[2], (m.n_experts,))
    r = moe_lib.route_sigmoid(x, w, m, bias)
    scores = np.asarray(jax.nn.sigmoid(x @ w), np.float64)
    want = _brute_force_routing(scores, np.asarray(bias, np.float64), m)
    for t, ref in enumerate(want):
        got = dict(zip(np.asarray(r.experts[t]).tolist(),
                       np.asarray(r.gates[t]).tolist()))
        assert set(got) == set(ref), t
        for e in ref:
            assert got[e] == pytest.approx(ref[e], rel=1e-5)
    np.testing.assert_allclose(np.asarray(r.gates).sum(-1),
                               m.routed_scaling, rtol=1e-5)


def test_the_bias_chooses_but_does_not_weigh():
    m = get_config("deepseek-v3").moe
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), (32, m.n_experts)) * 0.3
    plain = moe_lib.route_sigmoid(x, w, m)
    chosen = set(np.asarray(plain.experts[0]).tolist())
    lift = next(e for e in np.argsort(-np.asarray(plain.probs[0]))
                if int(e) not in chosen and int(e) // 32 in
                {int(c) // 32 for c in chosen})
    bias = jnp.zeros((m.n_experts,)).at[lift].set(1.0)
    biased = moe_lib.route_sigmoid(x, w, m, bias)
    now = np.asarray(biased.experts[0]).tolist()
    assert int(lift) in now and set(now) != chosen
    s = np.asarray(plain.probs[0])
    np.testing.assert_allclose(np.asarray(biased.gates[0]),
                               s[now] / s[now].sum() * m.routed_scaling,
                               rtol=1e-5)


def test_the_shares_of_every_chip_add_up_to_the_uncut_layer():
    """Each of the 4 shares of 16 routed experts computes its experts'
    part for the tokens routed to them; with the shared expert counted
    once they sum to the uncut layer."""
    whole = reduced(get_config("deepseek-v3"))
    p = jax.tree.map(lambda a: a[0], init_params(
        whole, jax.random.PRNGKey(6))["blocks"][0])
    x = jax.random.normal(jax.random.PRNGKey(7), (24, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want, _ = moe_lib.moe_ffn(p, x, whole.moe, "silu", "full")
        parts = []
        for i in range(4):
            share = dataclasses.replace(whole.moe, n_held=4, first_held=4 * i)
            pi = dict(p, **{k: p[k][4 * i:4 * i + 4]
                            for k in ("we1", "we3", "we2")})
            parts.append(moe_lib.routed_experts_dense(pi, x, share, "silu",
                                                      "full")[0])
        total = sum(parts) + gated_ffn(x, p["ws1"], p["ws3"], p["ws2"])
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert all(float(jnp.abs(y).max()) > 0 for y in parts)


def test_a_held_share_routes_only_its_own_experts():
    cfg = _toy(held=4)
    p = jax.tree.map(lambda a: a[0],
                     init_params(cfg, jax.random.PRNGKey(8))["blocks"][0])
    x = jax.random.normal(jax.random.PRNGKey(9), (32, cfg.d_model))
    r = moe_lib.route_for(p, x, cfg.moe)
    y, _ = moe_lib.routed_experts_dense(p, x, cfg.moe, "silu", "full")
    routed_here = np.any(np.asarray(r.experts) < 4, axis=-1)
    assert routed_here.any() and not routed_here.all()
    assert np.all(np.abs(np.asarray(y))[~routed_here] == 0)
    assert np.all(np.abs(np.asarray(y))[routed_here].max(-1) > 0)
    assert moe_lib.routed_rows(cfg.moe, 32, "full") == 4 * 32


def test_the_paged_layout_refuses_latent_attention():
    from repro.serving.pages import PageError, PagePool, paged_supported
    cfg = _toy()
    ok, why = paged_supported(cfg, 64, 16)
    assert not ok and "latent" in why
    with pytest.raises(PageError, match="latent"):
        PagePool(cfg, n_pages=8, page_size=16, max_seq=64)


def test_the_disaggregated_runtime_refuses_latent_attention(toy):
    from repro.core.disagg import DisaggregatedInstance
    cfg, params = toy
    with pytest.raises(NotImplementedError, match="latent attention"):
        DisaggregatedInstance(cfg, params)


@pytest.mark.parametrize("change,match", [
    (dict(n_held=2), "held experts"),
    (dict(scoring="sigmoid", n_experts=4, n_group=2, topk_group=1),
     "sigmoid")])
def test_the_disaggregated_runtime_and_m2n_refuse_what_they_do_not_route(
        change, match):
    """GQA layers whose MoE holds a share of the experts, or routes by
    sigmoid groups, are refused by name, not deep inside a shape."""
    from repro.core import m2n
    from repro.core.disagg import DisaggregatedInstance
    base = reduced(get_config("mixtral-8x22b"))
    cfg = dataclasses.replace(base, moe=dataclasses.replace(base.moe,
                                                            **change))
    params = init_params(cfg, jax.random.PRNGKey(10))
    with pytest.raises(NotImplementedError, match=match):
        DisaggregatedInstance(cfg, params)
    with pytest.raises(NotImplementedError, match=match):
        m2n.sharded_routed_experts(params["blocks"][0], None, cfg.moe,
                                   "silu", "full", mesh=None)
