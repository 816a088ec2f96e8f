"""Mosaic compiles of the serving hot path's Pallas kernels at
mixtral-8x22b's published widths, for a described (not attached) TPU
v5e: what interpret mode cannot show — block shapes Mosaic refuses,
primitives it cannot lower — fails here, without a chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch.parity import kernel_names

D, H, HKV, HD = 6144, 48, 8, 128        # mixtral-8x22b widths
E, F, TOP_K = 8, 16384, 2
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(chip, fn, *shapes) -> list:
    """Compile ``fn`` for the described chip; the Mosaic kernels in it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return kernel_names(jax.jit(fn).lower(*args).compile().as_text())


@pytest.mark.parametrize("W", [128, 2048])      # one KV block, and four
def test_decode_attention(chip, W):
    B = 4
    fn = lambda q, k, v, cp, p: ops.decode_attention(q, k, v, cp, p,
                                                     interpret=False)
    assert _compile(chip, fn, ((B, H, HD), BF), ((B, W, HKV, HD), BF),
                    ((B, W, HKV, HD), BF), ((B, W), I32),
                    ((B,), I32)) == ["decode_attention"]


@pytest.mark.parametrize("W", [512, 2048])      # one latent block, and four
def test_mla_decode_attention(chip, W):
    """DeepSeek-V3's absorbed latent decode: 128 heads over one
    (W, 512 + 64) latent row."""
    B, HL, DL, VL = 4, 128, 576, 512
    fn = lambda q, lat, cp, p: ops.mla_decode_attention(
        q, lat, cp, p, v_dim=VL, scale=0.1, interpret=False)
    assert _compile(chip, fn, ((B, HL, DL), BF), ((B, W, DL), BF),
                    ((B, W), I32), ((B,), I32)) == ["mla_decode_attention"]


def test_paged_decode_attention(chip):
    B, P, ps, n_logical = 4, 64, 16, 8
    fn = lambda q, k, v, pp, bt, p: ops.paged_decode_attention(
        q, k, v, pp, bt, p, interpret=False)
    assert _compile(chip, fn, ((B, H, HD), BF), ((P, ps, HKV, HD), BF),
                    ((P, ps, HKV, HD), BF), ((P, ps), I32),
                    ((B, n_logical), I32),
                    ((B,), I32)) == ["paged_decode_attention"]


@pytest.mark.parametrize("T", [4, 512])         # one token block, and two
@pytest.mark.parametrize("tables", [False, True])
def test_gating_dispatch(chip, T, tables):
    """Plain dispatch, and placement-table dispatch owner-filtered for
    one of two expert nodes (the m2n shard case, owner traced)."""
    if tables:
        R = 2
        fn = lambda x, w, o, rn, rs, rc: ops.gating_dispatch(
            x, w, TOP_K, n_buckets=E, capacity=T, owner=o,
            slots_per_node=E // 2, rep_node=rn, rep_slot=rs, rep_cum=rc,
            interpret=False)
        extra = (((), I32), ((E, R), I32), ((E, R), I32), ((E, R), F32))
    else:
        fn = lambda x, w: ops.gating_dispatch(x, w, TOP_K, n_buckets=E,
                                              capacity=T, interpret=False)
        extra = ()
    assert _compile(chip, fn, ((T, D), BF), ((D, E), F32),
                    *extra) == ["gating_dispatch"]


@pytest.mark.parametrize("k,n", [(D, F), (F, D)])  # expert up, expert down
def test_grouped_matmul(chip, k, n):
    C = 8
    fn = lambda x, w: ops.grouped_matmul(x, w, interpret=False)
    assert _compile(chip, fn, ((E, C, k), BF),
                    ((E, k, n), BF)) == ["grouped_matmul"]
