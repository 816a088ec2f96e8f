"""Inline admission as compiled programs: the request's prefill is one
program per prompt length, and its cache rows are written into the
engine's cache by one program shared by every slot, with the cache
donated.  Checked against eager ``prefill`` + ``insert_rows`` on reduced
mixtral and on reduced DeepSeek-V3 with its dense lead layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.config import chip_share, get_config, reduced
from repro.models import init_params, prefill
from repro.serving.config import ServingConfig
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import insert_rows

from test_multidevice import run_sub

MAX_BATCH, MAX_SEQ = 4, 32
CONFIGS = {
    "mixtral": lambda: reduced(get_config("mixtral-8x22b")),
    "deepseek": lambda: chip_share(reduced(get_config("deepseek-v3")),
                                   experts_held=4),
}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    cfg = CONFIGS[request.param]()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    return cfg, params


def _engine(cfg, params):
    return Engine(cfg, params, dtype=jnp.bfloat16,
                  config=ServingConfig(max_batch=MAX_BATCH, max_seq=MAX_SEQ))


def _prompt(cfg, n, seed):
    return np.random.RandomState(seed).randint(2, cfg.vocab, n).tolist()


def _rows(cache, row):
    """Every leaf's row ``row`` (batch axis 1 in the scanned blocks, 0 in
    the lead and remainder layers) and every leaf with that row removed,
    as host arrays."""
    mine, rest = [], []
    for key, entries in cache.items():
        axis = 1 if key == "blocks" else 0
        for a in jax.tree.leaves(entries):
            a = np.asarray(a.astype(jnp.float32))
            mine.append(np.take(a, row, axis=axis))
            rest.append(np.delete(a, row, axis=axis))
    return mine, rest


def _built(eng, span):
    return eng.obs.counters().get(f"{obs.BUILT}@{span}", 0)


def test_admission_matches_eager_prefill_and_insert(model):
    cfg, params = model
    eng = _engine(cfg, params)
    # a request already decoding in slot 0, so the other rows hold data
    eng.submit(Request(rid=0, prompt=_prompt(cfg, 9, 0), max_new_tokens=8))
    for _ in range(3):
        eng.step()
    before = jax.tree.map(np.asarray, eng.cache)
    prompt = _prompt(cfg, 6, 1)
    eng.submit(Request(rid=1, prompt=prompt, max_new_tokens=8))
    eng._admit()
    slot = eng.running[1].slot
    assert slot != 0

    logits, rcache = prefill(params, cfg, jnp.asarray([prompt], jnp.int32),
                             max_seq=MAX_SEQ)
    want = insert_rows(jax.tree.map(jnp.asarray, before), rcache, slot)
    assert eng.running[1].generated == [int(jnp.argmax(logits[0]))]
    got_row, got_rest = _rows(eng.cache, slot)
    want_row, _ = _rows(want, slot)
    _, before_rest = _rows(before, slot)
    # bf16 keeps 8 significant bits: the compiled program may round a
    # few intermediates differently from the eager one, so the row agrees
    # to 1/64 of each leaf's largest magnitude, not bit for bit
    for g, w in zip(got_row, want_row):
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2 ** -6 * np.abs(w).max())
    for g, b in zip(got_rest, before_rest):
        np.testing.assert_array_equal(g, b)


def test_one_program_per_prompt_length_for_every_slot(model):
    cfg, params = model
    eng = _engine(cfg, params)
    eng.submit(Request(rid=0, prompt=_prompt(cfg, 7, 2), max_new_tokens=4))
    eng._admit()
    prefill_built = _built(eng, "engine.prefill")
    insert_built = _built(eng, "engine.insert")
    eng.submit(Request(rid=1, prompt=_prompt(cfg, 7, 3), max_new_tokens=4))
    eng._admit()
    assert eng.running[0].slot != eng.running[1].slot
    assert _built(eng, "engine.prefill") == prefill_built
    assert _built(eng, "engine.insert") == insert_built
    eng.submit(Request(rid=2, prompt=_prompt(cfg, 13, 4), max_new_tokens=4))
    eng._admit()
    assert _built(eng, "engine.prefill") > prefill_built
    assert _built(eng, "engine.insert") == insert_built


def test_write_row_keeps_the_sharding_and_donates():
    """On 8 forced CPU devices: a cache sharded over its batch axis, and
    one replicated over four devices, come back with the sharding they
    went in with; the row matches ``insert_rows`` and the cache passed in
    is consumed."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.config import get_config, reduced
from repro.models import init_cache, init_params, prefill
from repro.serving.kvcache import insert_rows, write_row
cfg = reduced(get_config("mixtral-8x22b"))
params = init_params(cfg, jax.random.PRNGKey(0))
_, rcache = prefill(params, cfg, jnp.arange(2, 7)[None], max_seq=16)
mesh = Mesh(np.array(jax.devices()[:4]), ("a",))
for spec in (P(), P(None, "a")):
    def place(a):
        return jax.device_put(
            a, NamedSharding(mesh, spec if a.ndim > 2 else P()))
    cache = jax.tree.map(place, init_cache(cfg, 4, 16, jnp.float32))
    want = insert_rows(cache, rcache, 2)
    shardings = jax.tree.map(lambda a: a.sharding, cache)
    got = write_row(cache, rcache, 2)
    assert jax.tree.leaves(cache)[0].is_deleted()
    assert jax.tree.map(lambda a: a.sharding, got) == shardings
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
print("WRITE-ROW-SHARDING-OK")
""")
    assert "WRITE-ROW-SHARDING-OK" in out
