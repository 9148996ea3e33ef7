"""A block-diffusion model served by LLMEngine, second file (the first:
test_sdar_engine.py): what the threshold does to a block's passes; no
page in the prefix index and no row in a shipment before its last block
commits; save and restore.

One small float32 config (the engine's tokens ARE the reference's, so a
wrong row, mask or slot shows as a wrong token), page 16, chunk 16."""

import dataclasses
import time

import numpy as np
import pytest

from benchmarks import reference_sdar as ref
from ray_tpu.models.laguna import LagunaConfig
from ray_tpu.serve.llm import LLMEngine

CFG = LagunaConfig.tiny_blocks()
MASK = CFG.mask_token_id
SIZES = dict(num_hidden_layers=CFG.num_hidden_layers, head_dim=CFG.head_dim,
             rope_theta=1000000, rms_norm_eps=CFG.rms_norm_eps,
             num_experts_per_tok=CFG.num_experts_per_tok,
             norm_topk_prob=True,
             generation=dict(block_length=4, denoising_steps=4,
                             confidence_threshold=0.9, mask_token_id=MASK))


def _engine(cfg=CFG, **kw):
    kw = {"page_size": 16, "max_batch": 4, "prefill_chunk": 16,
          "prefill_lanes": 2, "seed": 3, **kw}
    return LLMEngine(cfg, **kw)


def _prompt(n, salt=0):
    rs = np.random.RandomState(1000 + 7 * n + salt)
    return [int(t) for t in rs.randint(1, MASK, n)]


def _passes(eng, rid):
    return eng._by_rid[rid].blk.passes


def _step_until(eng, cond, rounds=400):
    for _ in range(rounds):
        eng.step()
        if cond():
            return
    raise AssertionError("the engine never got there")


@pytest.mark.parametrize("threshold,per_block", [(0.0, 2), (2.0, 5)])
def test_the_threshold_decides_how_many_passes_a_block_takes(threshold,
                                                            per_block):
    """At 0 every masked position clears it: a block a pass, and its
    commit; at 2 none ever does: the schedule's four, and the commit."""
    cfg = dataclasses.replace(CFG, confidence_threshold=threshold)
    eng = _engine(cfg)
    reqs = [{"tokens": _prompt(n), "max_new_tokens": new,
             "request_id": f"t{n}", "record_passes": True}
            for n, new in ((8, 12), (21, 7))]
    outs = eng.generate_batch(reqs)
    for req, out in zip(reqs, outs):
        want = ref.generate(eng._params, req["tokens"],
                            req["max_new_tokens"], SIZES,
                            threshold=threshold)
        assert out == want["tokens"]
        assert _passes(eng, req["request_id"]) == want["passes"]
    first = [p for p in _passes(eng, "t8") if p[0] == 8]
    assert len(first) == per_block
    st = eng.stats()
    assert bool(st["block_tokens_over_threshold_total"]) == (threshold == 0)


def test_a_page_is_published_only_when_its_last_block_is_committed():
    """A 35-token prompt: pages 0 and 1 are whole prompt blocks and enter
    the prefix index with the chunk that completes them; the page that
    holds the prompt's tail and the generated blocks never does, not
    even once all its blocks are committed (its tokens are not the
    prompt's).  A second request with the same first 32 tokens then
    attaches to both pages, prefills nothing of them, and is the
    reference's."""
    eng = _engine()
    full = eng._groups["full"]
    prompt = _prompt(35)
    other = prompt[:32] + _prompt(7, salt=1)
    first = eng.submit({"tokens": prompt, "max_new_tokens": 30,
                        "request_id": "first"})
    second = None
    while not first.done:
        eng.step()
        if first.done:
            break
        # the pages registered, against the end of the last block whose
        # commit is dispatched (`pos`) at this moment
        held = [int(p) for p in first.cache["full"].pages]
        registered = [i for i, p in enumerate(held) if p in full._page_keys]
        assert all((i + 1) * 16 <= min(first.pos, len(prompt))
                   for i in registered)
        if first.pos >= 48 and second is None:
            assert registered == [0, 1]   # page 2's blocks are committed
            second = eng.submit({"tokens": other, "max_new_tokens": 6,
                                 "request_id": "second"})
    _step_until(eng, lambda: second.done)
    eng.drain()
    assert second.prefix_tokens == 32
    assert eng.stats()["prefix_tokens_shared"] == 32
    assert list(second.generated) == ref.generate(
        eng._params, other, 6, SIZES)["tokens"]
    assert not full.index and eng.stats()["kv_pages_in_use"] == {"full": 0}


def test_no_shipment_holds_an_open_blocks_rows():
    """Disaggregated prefill ships the rows of the prompt's whole blocks
    and no token; the engine that receives them opens the first block
    with the prompt's tail and is the reference's."""
    src, dst = _engine(), _engine()
    prompt = _prompt(22)
    payload = src.prefill_request({"tokens": prompt, "max_new_tokens": 6,
                                   "request_id": "ship"})
    meta = payload["meta"]
    assert (meta["n"], meta["first_token"], meta["pages"]) == (20, None, 2)
    assert src.stats()["kv_pages_in_use"] == {"full": 0}
    seq = dst.submit({"tokens": prompt, "max_new_tokens": 6,
                      "request_id": "ship"},
                     kv_pack=(meta, payload["rows"]))
    _step_until(dst, lambda: seq.done)
    dst.drain()
    st = dst.stats()
    assert st["prefill_steps"] == 0 and st["kv_pages_shipped_in"] == 2
    assert list(seq.generated) == ref.generate(
        dst._params, prompt, 6, SIZES)["tokens"]
    with pytest.raises(ValueError, match="shorter than a block"):
        src.prefill_request({"tokens": [1, 2], "max_new_tokens": 2})


def test_save_and_restore_round_trip_mid_answer():
    eng = _engine()
    prompt = _prompt(13)
    seq = eng.submit({"tokens": prompt, "max_new_tokens": 17,
                      "request_id": "keep"})
    _step_until(eng, lambda: len(seq.generated) >= 7)
    state = eng.save_state()
    known = list(state["seqs"][0]["generated"])
    assert (len(prompt) + len(known)) % 4 == 0   # whole blocks only
    fresh = _engine()
    fresh.restore_state(state)
    again = fresh._by_rid["keep"]
    _step_until(fresh, lambda: again.done)
    fresh.drain()
    assert list(again.generated) == ref.generate(
        eng._params, prompt, 17, SIZES)["tokens"]


