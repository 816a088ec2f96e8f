"""The per-row inputs the engine hands its decode call.

Every decode step receives ``toks`` and ``pos`` as one ``(max_batch,)``
array each, built on the host from the running requests: a running row
holds its request's latest token at position ``len(prompt) +
len(generated) - 1``, and a free slot decodes at position 0.  A churned
batch (slots retiring and refilling mid-run, with free slots left over)
must keep every entry right, and its greedy tokens must equal those of
each request decoded alone.
"""
import jax
import numpy as np
import pytest

from repro.config import get_config, reduced
from repro.models import init_params
from repro.serving.config import ServingConfig
from repro.serving.engine import Engine, Request

MAX_BATCH = 4


@pytest.fixture(scope="module")
def moe_setup():
    cfg = reduced(get_config("qwen2-moe-a2.7b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg):
    """Mixed prompt and output lengths: six requests through four slots,
    so short ones retire and their slots refill while long ones run."""
    rng = np.random.RandomState(5)
    shape = [(3, 6), (9, 2), (5, 4), (12, 1), (4, 5), (7, 3)]
    return [Request(rid=i, prompt=rng.randint(2, cfg.vocab, L).tolist(),
                    max_new_tokens=n) for i, (L, n) in enumerate(shape)]


def _engine(cfg, params, kv_layout):
    sc = ServingConfig(max_batch=MAX_BATCH, max_seq=32, page_size=8,
                       kv_layout=kv_layout, verbose=False)
    return Engine(cfg, params, config=sc)


def _record_inputs(eng):
    """Wrap the engine's decode call; each step appends the ``toks`` and
    ``pos`` it received with the slot -> request map of that moment."""
    seen = []
    decode = eng._decode

    def recording(toks, cache, pos):
        rows = {r.slot: (r.rid, len(r.prompt) + len(r.generated) - 1,
                         r.generated[-1]) for r in eng.running.values()}
        seen.append((np.asarray(toks), np.asarray(pos), rows))
        return decode(toks, cache, pos)
    eng._decode = recording
    return seen


@pytest.mark.parametrize("kv_layout", ["contiguous", "paged"])
def test_decode_receives_each_rows_position(moe_setup, kv_layout):
    cfg, params = moe_setup
    eng = _engine(cfg, params, kv_layout)
    seen = _record_inputs(eng)
    for req in _requests(cfg):
        eng.submit(req)
    batched = {r.rid: r.generated for r in eng.run_until_done(max_iters=100)}

    slot_owners = {}
    free_seen = 0
    for toks, pos, rows in seen:
        assert toks.shape == pos.shape == (MAX_BATCH,)
        assert pos.dtype == toks.dtype == np.int32
        for slot in range(MAX_BATCH):
            if slot in rows:
                rid, want_pos, want_tok = rows[slot]
                assert pos[slot] == want_pos, (slot, rid)
                assert toks[slot] == want_tok, (slot, rid)
                slot_owners.setdefault(slot, set()).add(rid)
            else:
                assert pos[slot] == 0, slot
                free_seen += 1
    # the batch really churned: a slot served two requests, and some
    # step decoded with a free slot beside running rows
    assert any(len(owners) > 1 for owners in slot_owners.values())
    assert free_seen > 0

    for req in _requests(cfg):
        alone = _engine(cfg, params, kv_layout)
        alone.submit(req)
        (done,) = alone.run_until_done(max_iters=100)
        assert done.generated == batched[req.rid], req.rid
