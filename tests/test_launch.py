"""The serving launcher's published-width path on CPU: a depth cut in
bfloat16 served by the monolithic and the ping-pong kernel runtimes
(the chip smoke's comparison, at a reduced size), and the persistent
compile cache's directory."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache, parity
from repro.serving.config import ServingConfig


def test_depth_cut_bf16_runtimes_agree():
    base = ServingConfig(arch="mixtral-8x22b", use_reduced=True, n_layers=1,
                         dtype="bfloat16", n_requests=4, max_new=3,
                         max_batch=4, max_seq=64, prompt_len=8,
                         microbatches=2, verbose=False)
    a = parity.serve(base.with_overrides(runtime="monolithic"))
    b = parity.serve(base.with_overrides(runtime="pingpong",
                                         use_kernels=True))
    for s in (a, b):
        assert s.failures() == []
        assert s.first_logits.shape == (4, 512)
    # one layer: each decode step ran one attention stage per micro-batch
    st = b.stats["stages"]
    assert st["attn_n"] == 2 * b.stats["decode_iters"]
    assert parity.logits_gap(a, b) <= parity.LOGIT_TOL


def test_parity_needs_every_request_in_the_first_step():
    with pytest.raises(ValueError):
        parity.serve(ServingConfig(n_requests=5, max_batch=4))


def test_compile_cache_dir(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        # set: JAX reads the variable itself, the helper sets nothing
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        # unset: a fixed directory inside the checkout
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.enable_compile_cache()
        repo = Path(__file__).resolve().parents[1]
        assert path == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
