"""The benchmark's float32 reference of the mixtral and DBRX family
(``references/moe_gqa.py``) and its weights, against the program at a toy
size on the CPU, and the numerics every family shares
(``reference.py``)."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.references import moe_gqa

TOY = moe_gqa.Dims(d_model=256, n_heads=4, n_kv_heads=1, head_dim=64, n_experts=4,
           top_k=2, d_ff_expert=128, vocab=512, n_layers=2)


def _program_weights(seed, dtype):
    from repro.config import get_config, reduced
    from repro.models import init_params
    cfg = reduced(get_config("mixtral-8x22b"))
    return cfg, jax.jit(init_params, static_argnums=(0, 2))(
        cfg, jax.random.PRNGKey(seed), dtype)


def test_weights_are_the_launchers_draw():
    seed = 2**31 + 3
    cfg, p = _program_weights(seed, jnp.bfloat16)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff_expert,
            cfg.vocab, cfg.n_layers) == (256, 4, 1, 64, 4, 2, 128, 512, 2)
    w = moe_gqa.init_weights(TOY, seed, jnp.bfloat16)
    for a, b in [(w["embed"], p["embed"]), (w["lm_head"], p["lm_head"]),
                 (w["final_norm"], p["final_norm"])]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    blk = p["blocks"][0]
    for k in ("ln1", "ln2", "wq", "wk", "wv", "wo", "router", "we1", "we3",
              "we2"):
        assert w["layers"][k].dtype == blk[k].dtype, k
        np.testing.assert_array_equal(np.asarray(w["layers"][k]),
                                      np.asarray(blk[k]), err_msg=k)


def test_reference_matches_the_programs_forward_pass():
    """At float32 on the CPU, the program's prefill logits over a prompt
    agree with the reference's at every position to rounding."""
    from repro.models import prefill
    seed = 7
    cfg, p = _program_weights(seed, jnp.float32)
    w = moe_gqa.init_weights(TOY, seed, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 512, 24).astype(np.int32)
    ref = np.asarray(moe_gqa.forward(w, TOY, jnp.asarray(toks))[0])
    for n in (1, 9, 24):
        with jax.default_matmul_precision("highest"):
            last, _ = prefill(p, cfg, jnp.asarray(toks[None, :n]), max_seq=32)
        np.testing.assert_allclose(np.asarray(last)[0], ref[n - 1],
                                   rtol=2e-4, atol=2e-4)


def test_served_gaps_are_zero_for_the_references_own_choices():
    w = moe_gqa.init_weights(TOY, 5, jnp.float32)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 512, 16),
                       jnp.int32)
    ref = moe_gqa.forward(w, TOY, toks)[0]
    best = jnp.argmax(ref, axis=-1).astype(jnp.int32)
    gap, low, margin = reference.served_gaps(moe_gqa.forward, w, TOY, toks, best, True)
    assert float(jnp.max(gap)) == 0.0
    assert float(jnp.max(low)) >= 0.0
    assert margin.shape == (16,) and float(jnp.min(margin)) >= 0.0
    worst = jnp.argmin(ref, axis=-1).astype(jnp.int32)
    gap, _, _ = reference.served_gaps(moe_gqa.forward, w, TOY, toks, worst, False)
    np.testing.assert_allclose(np.asarray(gap),
                               np.asarray(ref.max(-1) - ref.min(-1)),
                               rtol=1e-6)


def test_float8_rounding_is_coarser_than_bfloat16():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    err8 = float(jnp.max(jnp.abs(reference._fp8(x) - x)))
    err16 = float(jnp.max(jnp.abs(x.astype(jnp.bfloat16).astype(
        jnp.float32) - x)))
    assert err8 > 4 * err16


def test_tie_margin_is_the_gap_below_the_last_chosen_expert():
    router = jnp.asarray([[3.0, 1.0, 2.5, 0.0], [1.0, 1.0, 1.0, 0.9]])
    np.testing.assert_allclose(np.asarray(reference._tie_margin(router, 2)),
                               [1.5, 0.0])
    np.testing.assert_allclose(np.asarray(reference._tie_margin(router, 1)),
                               [0.5, 0.0])
