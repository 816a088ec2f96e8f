"""The program's spans and counters (``repro.obs``): totals, nesting, the
host-clock window, the program-build listener, and the engine's and the
ping-pong runtime's readings built on them."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.config import get_config, reduced
from repro.core.disagg import DisaggPlan, DisaggregatedInstance
from repro.models import init_params
from repro.serving.config import ServingConfig
from repro.serving.engine import Engine, Request


def test_spans_nest_and_total():
    r = obs.Recorder()
    with r.span("outer", step=3) as outer:
        for i in range(2):
            with r.span("inner", i=i):
                time.sleep(0.002)
    tot = r.totals()
    assert tot["inner"][0] == 2 and tot["outer"][0] == 1
    assert tot["inner"][1] >= 0.004
    assert tot["outer"][1] >= tot["inner"][1]
    assert outer.seconds == pytest.approx(tot["outer"][1])
    assert r.seconds("inner") == tot["inner"][1]
    assert r.seconds("never") == 0.0


def test_a_span_closes_on_an_exception():
    r = obs.Recorder()
    with pytest.raises(ValueError):
        with r.span("failing"):
            raise ValueError("boom")
    assert r.totals()["failing"][0] == 1
    with r.span("after"):
        r.count("n")
    assert r.counters() == {"n": 1}


def test_counters_window_and_reset():
    r = obs.Recorder()
    r.count("rows", 5)
    t0 = time.perf_counter()
    with r.span("step"):
        r.count("rows", 3)
    r.count("steps")
    t1 = time.perf_counter()
    r.count("rows", 7)
    assert r.counters() == {"rows": 15, "steps": 1}
    totals, counters = obs.window(t0, t1)
    assert counters == {"rows": 3, "steps": 1}
    assert totals["step"][0] == 1
    r.reset()
    assert r.totals() == {} and r.counters() == {}
    assert obs.window(t0, t1) == ({}, {})


def test_programs_built_on_a_new_shape_only():
    r = obs.Recorder()
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 0.25)
    with r.span("first"):
        f(jnp.ones((7, 3))).block_until_ready()
    built = r.counters().get(obs.BUILT, 0)
    assert built >= 1
    assert r.counters()[f"{obs.BUILT}@first"] == built
    with r.span("repeat"):
        f(jnp.ones((7, 3))).block_until_ready()
    assert r.counters()[obs.BUILT] == built
    with r.span("new_shape"):
        f(jnp.ones((5, 3))).block_until_ready()
    assert r.counters()[obs.BUILT] > built
    assert r.counters()[f"{obs.BUILT}@new_shape"] >= 1
    assert f"{obs.BUILT}@repeat" not in r.counters()


@pytest.fixture(scope="module")
def moe_setup():
    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _requests(cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(2, cfg.vocab,
                                       size=rng.randint(2, 10)).tolist(),
                    max_new_tokens=int(rng.randint(2, 6))) for i in range(n)]


def test_engine_counts_one_sync_per_row_and_admission(moe_setup):
    cfg, params = moe_setup
    eng = Engine(cfg, params, config=ServingConfig(max_batch=3, max_seq=32))
    for req in _requests(cfg, 6):
        eng.submit(req)
    rows = admitted = steps = 0
    while eng.outstanding:
        before = eng.n_prefills
        n = eng.step()
        rows += n
        admitted += eng.n_prefills - before
        steps += n > 0
    c = eng.counters()
    # one read of the batch's tokens per decode step, one per admission
    assert c["host_syncs"] == steps + admitted
    assert c["admissions"] == admitted == 6
    assert c["decode_steps"] == steps == eng.n_decode_iters
    assert c[obs.BUILT] >= c[obs.FROM_CACHE] >= 0
    st = eng.stats()
    assert st["counters"] == c
    ph = st["phases"]
    assert set(ph) == {"transfer_s", "transfer_n", "transfer_mode",
                       "decode_s", "decode_n", "prefill_s", "prefills"}
    assert ph["prefill_s"] > 0 and ph["transfer_s"] > 0 \
        and ph["decode_s"] > 0
    assert ph["transfer_n"] == ph["prefills"] == 6
    assert ph["decode_n"] == steps
    tot = eng.obs.totals()
    assert tot["engine.step"][0] >= steps
    for name in ("engine.prepare", "engine.decode", "engine.sample"):
        assert tot[name][0] == steps


def test_pingpong_stage_report_reads_the_runtime_spans(moe_setup):
    cfg, params = moe_setup
    inst = DisaggregatedInstance(cfg, params,
                                 plan=DisaggPlan(n_microbatches=2))
    eng = Engine(cfg, params, runtime=inst,
                 config=ServingConfig(max_batch=4, max_seq=32,
                                      runtime="pingpong"))
    for req in _requests(cfg, 4, seed=1):
        eng.submit(req)
    eng.run_until_done()
    rep = eng.stats()["stages"]
    stages = ("attn", "m2n", "expert", "n2m", "combine")
    assert set(rep) == ({f"{s}_s" for s in stages}
                        | {f"{s}_n" for s in stages} | {"t_a", "t_e", "t_c"})
    steps = eng.n_decode_iters
    for s in stages:
        assert rep[f"{s}_n"] == steps * 2 * cfg.n_layers
        assert rep[f"{s}_s"] > 0
    assert rep["t_a"] > 0 and rep["t_e"] > 0 and rep["t_c"] > 0
    inst.reset_stage_times()
    assert inst.stage_report()["attn_n"] == 0
