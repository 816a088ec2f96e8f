"""Multi-device validation in a subprocess with forced host devices.

The dry-run flag (--xla_force_host_platform_device_count) must not leak
into the main test process (smoke tests expect 1 device), so these tests
spawn a fresh interpreter with 8 placeholder devices and run:

  * the disaggregated runtime on 4 attention + 4 expert devices,
    asserting token-for-token equality with the monolithic path;
  * the M2N shard_map dispatch on a (2, 4) mesh vs the dense oracle;
  * a miniature dry-run (lower + compile) on a (2, 4) mesh.
"""
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=420):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_disagg_8_devices_matches_monolithic():
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.config import get_config, reduced
from repro.core.disagg import DisaggPlan, DisaggregatedInstance
from repro.models import decode_step, init_params, prefill
cfg = reduced(get_config("mixtral-8x22b"))
params = init_params(cfg, jax.random.PRNGKey(0))
B, T = 4, 8
toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
last, cache = prefill(params, cfg, toks, max_seq=16)
nxt = jnp.argmax(last, -1)
pos = jnp.full((B,), T, jnp.int32)
want, _ = decode_step(params, cfg, nxt, cache, pos)
devs = jax.devices()
inst = DisaggregatedInstance(cfg, params, attn_devices=devs[:4],
                             expert_devices=devs[4:],
                             plan=DisaggPlan(n_microbatches=2))
got, _ = inst.decode_step(nxt, cache, pos)
np.testing.assert_allclose(np.asarray(got, np.float32),
                           np.asarray(want, np.float32), rtol=3e-4, atol=3e-4)
print("DISAGG-8DEV-OK attn_mesh=%s expert_mesh=%s" %
      (inst.attn_mesh.shape, inst.expert_mesh.shape))
""")
    assert "DISAGG-8DEV-OK" in out


def test_pingpong_engine_8_devices_token_identical():
    """The acceptance bar for PR 1: on a 4 attention + 4 expert device
    split, ping-pong serving with m=2 (through the M2N dispatch) emits
    exactly the monolithic engine's tokens and reports stage timings."""
    out = run_sub("""
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.config import get_config, reduced
from repro.core.disagg import DisaggPlan, DisaggregatedInstance
from repro.models import init_params
from repro.serving.engine import Engine, Request
cfg = reduced(get_config("mixtral-8x22b"))
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(0)
prompts = [rng.randint(2, cfg.vocab, size=rng.randint(2, 8)).tolist()
           for _ in range(5)]
def serve(**kw):
    eng = Engine(cfg, params, max_batch=4, max_seq=64, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    return {r.rid: r.generated for r in eng.run_until_done()}, eng
mono, _ = serve()
devs = jax.devices()
inst = DisaggregatedInstance(cfg, params, attn_devices=devs[:4],
                             expert_devices=devs[4:],
                             plan=DisaggPlan(n_microbatches=2, use_m2n=True))
pp, eng = serve(mode="pingpong", runtime=inst)
assert pp == mono, (pp, mono)
rep = eng.stats()["stages"]
assert rep["attn_n"] > 0 and rep["expert_n"] > 0
print("PINGPONG-8DEV-OK t_a=%.2e t_e=%.2e t_c=%.2e" %
      (rep["t_a"], rep["t_e"], rep["t_c"]))
""")
    assert "PINGPONG-8DEV-OK" in out


def test_prefill_cluster_8_devices_token_identical():
    """PR-2 tentpole acceptance: 2 prefill + 6 decode (2 attention +
    4 expert) disjoint device groups, KV rows migrated into the decode
    cache at admission — token-identical to the inline-prefill engine
    under both sync and async transfer."""
    out = run_sub("""
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.config import get_config, reduced
from repro.core.disagg import DisaggPlan, DisaggregatedInstance
from repro.launch.mesh import split_serving_devices
from repro.models import init_params
from repro.serving.engine import Engine, Request
from repro.serving.prefill import PrefillWorker
cfg = reduced(get_config("mixtral-8x22b"))
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(0)
prompts = [rng.randint(2, cfg.vocab, size=rng.randint(2, 8)).tolist()
           for _ in range(5)]
def serve(**kw):
    eng = Engine(cfg, params, max_batch=4, max_seq=64, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    return {r.rid: r.generated for r in eng.run_until_done()}, eng
mono, _ = serve()
prefill_devs, decode_devs = split_serving_devices(2)
assert len(prefill_devs) == 2 and len(decode_devs) == 6
assert not set(prefill_devs) & set(decode_devs), "clusters must be disjoint"
for transfer in ("sync", "async"):
    # expert group must divide n_experts (4 reduced): 2 attn + 4 expert
    inst = DisaggregatedInstance(cfg, params,
                                 attn_devices=decode_devs[:2],
                                 expert_devices=decode_devs[2:],
                                 plan=DisaggPlan(n_microbatches=2,
                                                 use_m2n=True))
    assert not (set(inst.attn_mesh.devices.flat) |
                set(inst.expert_mesh.devices.flat)) & set(prefill_devs)
    w = PrefillWorker(cfg, params, prefill_devs, max_seq=64)
    pp, eng = serve(mode="pingpong", runtime=inst, prefill_worker=w,
                    transfer=transfer, kv_sharding=inst.kv_sharding)
    assert pp == mono, (transfer, pp, mono)
    ph = eng.stats()["phases"]
    assert ph["prefill_devices"] == 2 and ph["transfer_n"] == 5
    assert ph["transfer_mode"] == transfer
print("PREFILL-CLUSTER-8DEV-OK")
""")
    assert "PREFILL-CLUSTER-8DEV-OK" in out


def test_rebalanced_placement_8_devices_token_identical():
    """PR-3 tentpole acceptance: on a 4 attention + 4 expert device
    split under a zipf(1.2)-skewed routing trace, the live-rebalanced
    engine (hot-expert replication on) emits exactly the static
    engine's tokens, replicates at least one hot expert, and reports a
    strictly lower placement imbalance."""
    out = run_sub("""
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.config import get_config, reduced
from repro.core.disagg import DisaggPlan, DisaggregatedInstance
from repro.launch.serve import _inject_router_bias, zipf_router_bias
from repro.models import init_params
from repro.serving.engine import Engine, Request
cfg = reduced(get_config("mixtral-8x22b"))
params = init_params(cfg, jax.random.PRNGKey(0))
params = _inject_router_bias(params, cfg,
                             zipf_router_bias(cfg.moe.n_experts, 1.2))
rng = np.random.RandomState(0)
prompts = [rng.randint(2, cfg.vocab, size=rng.randint(2, 8)).tolist()
           for _ in range(6)]
devs = jax.devices()
def serve(use_m2n=False, **kw):
    inst = DisaggregatedInstance(cfg, params, attn_devices=devs[:4],
                                 expert_devices=devs[4:],
                                 plan=DisaggPlan(n_microbatches=2,
                                                 use_m2n=use_m2n))
    eng = Engine(cfg, params, max_batch=4, max_seq=64, mode="pingpong",
                 runtime=inst, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    return {r.rid: r.generated for r in eng.run_until_done()}, eng.stats()
static_toks, static_stats = serve()
for use_m2n in (False, True):
    toks, stats = serve(use_m2n=use_m2n, expert_rebalance_every=2)
    assert toks == static_toks, (use_m2n, toks, static_toks)
    assert stats["rebalances"] > 0
    assert stats["replicated_experts"] >= 1, stats
    assert stats["imbalance"] < static_stats["imbalance"], (
        stats["imbalance"], static_stats["imbalance"])
print("REBALANCE-8DEV-OK static_imb=%.2f rebal_imb=%.2f" %
      (static_stats["imbalance"], stats["imbalance"]))
""")
    assert "REBALANCE-8DEV-OK" in out


def test_kernel_path_8_devices_token_identical():
    """Kernel-path acceptance: on the 4 attention + 4 expert split with
    a zipf-skewed router, the Pallas hot path (flash decode attention,
    fused gating+dispatch, grouped expert MLP) composed with m2n AND
    live expert rebalancing (placement tables) emits exactly the jnp
    static engine's tokens, and stats record the kernel mode."""
    out = run_sub("""
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.config import get_config, reduced
from repro.core.disagg import DisaggPlan, DisaggregatedInstance
from repro.launch.serve import _inject_router_bias, zipf_router_bias
from repro.models import init_params
from repro.serving.engine import Engine, Request
cfg = reduced(get_config("mixtral-8x22b"))
params = init_params(cfg, jax.random.PRNGKey(0))
params = _inject_router_bias(params, cfg,
                             zipf_router_bias(cfg.moe.n_experts, 1.2))
rng = np.random.RandomState(0)
prompts = [rng.randint(2, cfg.vocab, size=rng.randint(2, 8)).tolist()
           for _ in range(5)]
devs = jax.devices()
def serve(use_m2n=False, use_kernels=False, **kw):
    inst = DisaggregatedInstance(cfg, params, attn_devices=devs[:4],
                                 expert_devices=devs[4:],
                                 plan=DisaggPlan(n_microbatches=2,
                                                 use_m2n=use_m2n,
                                                 use_kernels=use_kernels))
    eng = Engine(cfg, params, max_batch=4, max_seq=64, mode="pingpong",
                 runtime=inst, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    return {r.rid: r.generated for r in eng.run_until_done()}, eng.stats()
ref_toks, ref_stats = serve()
assert ref_stats["use_kernels"] is False
for use_m2n in (False, True):
    toks, stats = serve(use_m2n=use_m2n, use_kernels=True,
                        expert_rebalance_every=2)
    assert toks == ref_toks, (use_m2n, toks, ref_toks)
    assert stats["use_kernels"] is True
    assert stats["rebalances"] > 0
    assert stats["replicated_experts"] >= 1, stats
print("KERNELS-8DEV-OK")
""")
    assert "KERNELS-8DEV-OK" in out


def test_paged_kv_8_devices_token_identical():
    """PR-6 tentpole acceptance: on the 4 attention + 4 expert split,
    the paged KV layout (page pool + radix prefix cache) through the
    ping-pong + M2N runtime emits exactly the contiguous engine's
    tokens, and the shared-prefix workload registers radix hits."""
    out = run_sub("""
import jax, numpy as np
assert jax.device_count() == 8, jax.device_count()
from repro.config import get_config, reduced
from repro.core.disagg import DisaggPlan, DisaggregatedInstance
from repro.models import init_params
from repro.serving.config import ServingConfig
from repro.serving.engine import Engine, Request
cfg = reduced(get_config("mixtral-8x22b"))
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.RandomState(0)
head = rng.randint(2, cfg.vocab, size=16).tolist()   # 2 shared pages
prompts = [head + rng.randint(2, cfg.vocab, size=rng.randint(3, 8)).tolist()
           for _ in range(5)]
devs = jax.devices()
def serve(layout):
    inst = DisaggregatedInstance(cfg, params, attn_devices=devs[:4],
                                 expert_devices=devs[4:],
                                 plan=DisaggPlan(n_microbatches=2,
                                                 use_m2n=True))
    sc = ServingConfig(max_batch=4, max_seq=64, runtime="pingpong",
                       kv_layout=layout, page_size=8, verbose=False)
    eng = Engine(cfg, params, config=sc, runtime=inst)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    return {r.rid: r.generated for r in eng.run_until_done()}, eng.stats()
contig, _ = serve("contiguous")
paged, stats = serve("paged")
assert paged == contig, (paged, contig)
assert stats["kv_layout"] == "paged"
assert stats["kv_pages"]["high_water"] > 0
pc = stats["prefix_cache"]
assert pc["hits"] > 0 and pc["hit_tokens"] > 0, pc
print("PAGED-8DEV-OK hits=%d hit_tokens=%d" % (pc["hits"], pc["hit_tokens"]))
""")
    assert "PAGED-8DEV-OK" in out


def test_m2n_sharded_dispatch_2x4_mesh():
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.config import MoEConfig
from repro.core import m2n
from repro.models import moe as moe_lib
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = MoEConfig(n_experts=6, top_k=2, d_ff_expert=16)   # 6 % 4 != 0 -> pad
key = jax.random.PRNGKey(0)
d, T = 8, 32
ks = jax.random.split(key, 5)
params = {"router": jax.random.normal(ks[0], (d, 6)),
          "we1": jax.random.normal(ks[1], (6, d, 16)) * 0.2,
          "we3": jax.random.normal(ks[2], (6, d, 16)) * 0.2,
          "we2": jax.random.normal(ks[3], (6, 16, d)) * 0.2}
x = jax.random.normal(ks[4], (T, d))
want, aux_w = moe_lib.routed_experts_dense(params, x, cfg, "silu", "full")
with mesh:
    got, aux = jax.jit(lambda p, x: m2n.sharded_routed_experts(
        p, x, cfg, "silu", "full", mesh=mesh, data_axes=("data",),
        expert_axis="model"))(params, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-4, atol=1e-4)
# aux is a per-data-shard estimator under shard_map (GShard computes the
# balance loss per group) — close to but not identical with the global one
np.testing.assert_allclose(float(aux), float(aux_w), rtol=0.05)
print("M2N-2x4-OK")
""")
    assert "M2N-2x4-OK" in out


def test_mini_dryrun_2x4_mesh():
    """lower+compile decode on a small mesh with the same sharding rules
    as the production dry-run (fast enough for CI)."""
    out = run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.config import get_config, reduced, INPUT_SHAPES
from repro.launch import sharding as shlib
from repro.launch.mesh import make_mesh
from repro.models import stubs
from repro.models.transformer import decode_step, init_params
mesh = make_mesh((2, 4), ("data", "model"))
cfg = reduced(get_config("qwen2-moe-a2.7b"))
B, S = 8, 64
pstructs = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0),
                                              jnp.bfloat16))
psh = shlib.to_shardings(mesh, shlib.param_specs(cfg, pstructs, mesh))
cstructs = stubs.cache_specs(cfg, B, S, jnp.bfloat16)
csh = shlib.to_shardings(mesh, shlib.cache_specs(cfg, cstructs, mesh, B))
tok = jax.ShapeDtypeStruct((B,), jnp.int32)
tok_sh = NamedSharding(mesh, shlib.input_spec(tok.shape, mesh))
with mesh:
    f = jax.jit(lambda p, t, c, pos: decode_step(p, cfg, t, c, pos, "full"),
                in_shardings=(psh, tok_sh, csh, tok_sh))
    compiled = f.lower(pstructs, tok, cstructs, tok).compile()
cost = compiled.cost_analysis()
assert cost.get("flops", 0) > 0
print("MINI-DRYRUN-OK flops=%.2e" % cost["flops"])
""")
    assert "MINI-DRYRUN-OK" in out
