"""The sparse setting of the latent-attention family THROUGH THE ENGINE:
chunked prefill and decode over both pools of a row (`latent`,
`index`), prefix sharing and the copy-on-write split, page shipping, and
what the engine counts of the selection — against the plain float32
reference's full forward (benchmarks/reference_glm.py)."""

import numpy as np
import pytest

from benchmarks import reference_glm as ref
from ray_tpu.models.pangu import SPARSE_COUNTERS
from ray_tpu.serve.llm import LLMEngine
from test_glm_model import CFG, PAGE, SIZES, TOKENS

NEW = 4


def _engine(**kw):
    # (two prefill lanes: no narrow program, so a pass is as wide as its
    # longest context and the first chunks take the dense path)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("prefill_lanes", 2)
    return LLMEngine(CFG, seed=5, page_size=PAGE, max_batch=4, **kw)


def _drain(eng):
    while eng.step():
        pass
    eng.drain()


def _greedy(params, prompt, new):
    """The reference's greedy continuation, a full forward a token, and
    the two margins of every position it judged."""
    toks, margins = list(prompt), []
    for _ in range(new):
        logits, router, picked = ref.logits(params, toks, SIZES,
                                            at=[len(toks) - 1])
        toks.append(int(np.argmax(np.asarray(logits[0]))))
        margins.append((float(router[0]), float(picked[0])))
    return toks[len(prompt):], margins


@pytest.fixture(scope="module")
def alone():
    return _engine(prefix_sharing=False)


@pytest.mark.parametrize("n", [40, 150, 230])
def test_chunks_then_decode_is_the_references_greedy_answer(alone, n):
    """A prompt inside the dense path (40 rows: nothing is selected), one
    past `index_topk` into the 256-column prefill pass, and one whose
    decode steps cross into the 16-page table: the tokens the
    reference's full forward picks, through chunks of 16, both pools and
    every form of the attention."""
    prompt = [int(t) for t in TOKENS[:n]]
    want, margins = _greedy(alone._params, prompt, NEW)
    # (a selection margin of 0 is an exact tie, which both break alike)
    assert min(router for router, _picked in margins) > 1e-6
    got = alone.generate_batch([{"tokens": prompt, "max_new_tokens": NEW}])
    assert got[0] == want


def test_widths_on_both_sides_of_index_topk(alone):
    # prefill: 64 columns take the dense path, 256 and 512 select;
    # decode: a table of 4 pages is dense, 16 and 32 (no more pages
    # than `index_topk` rows) walk the lane's pages under the mask
    assert alone._prefill_widths == [64, 256, 512]
    assert alone._paged_width_buckets() == [4, 16, 32]
    assert alone._model.counters[-6:] == SPARSE_COUNTERS


def test_both_parts_of_a_page_are_shared_split_and_shipped(alone):
    """A request that shares a live prefix — whole pages of BOTH pools,
    and 15 rows of a further page by a copy-on-write split that copies
    its latent rows AND its index keys — answers as an unshared one: its
    queries score the shared pages' index keys.  Rows shipped from a
    prefill engine, both parts, decode as local ones do."""
    prompt = [int(t) for t in TOKENS[:208]]
    want = alone.generate_batch(
        [{"tokens": prompt, "max_new_tokens": NEW}])[0]
    eng = _engine(params=alone._params)
    first = eng.submit({"tokens": prompt, "max_new_tokens": NEW})
    for _ in range(14):
        eng.step()
    second = eng.submit({"tokens": prompt, "max_new_tokens": NEW})
    _drain(eng)
    st = eng.stats()
    assert st["prefix_sharing"] and st["prefix_hits"] == 1
    assert st["cow_splits"] == 1 and st["prefix_tokens_shared"] == 207
    assert list(first.generated) == list(second.generated) == want
    layers = CFG.num_hidden_layers
    slots = eng.num_pages * PAGE
    assert st["latent_pool_bytes"] == layers * slots * 128 * 4
    assert st["index_pool_bytes"] == layers * slots * 16 * 4
    rep = eng.device_report()
    assert rep["index_pool_bytes"] == st["index_pool_bytes"]
    assert rep["kv_pool_bytes"] == st["latent_pool_bytes"] \
        + st["index_pool_bytes"]
    assert rep["model"]["cache_spec"] == [["full", 0, 40, 16]] * layers
    assert st["latent_pages_in_use"] == 0

    payload = alone.prefill_request({"tokens": prompt,
                                     "max_new_tokens": NEW,
                                     "request_id": "ship"})
    assert sorted(payload["rows"]) == ["index", "latent"]
    assert payload["rows"]["index"][0].shape == (208, 16)
    decoder = _engine(params=alone._params)
    shipped = decoder.submit(
        {"tokens": prompt, "max_new_tokens": NEW, "request_id": "ship"},
        kv_pack=(payload["meta"], payload["rows"]))
    _drain(decoder)
    assert list(shipped.generated) == want
    assert decoder.stats()["prefill_steps"] == 0
    # a stale index key is not what the selection reads: the same
    # request on an engine whose index pools hold other keys answers
    # the same, because a chunk's keys are written before they are read
    dirty = _engine(params=alone._params, prefix_sharing=False)
    dirty._pools = {**dirty._pools, "index": [
        p + 3.0 for p in dirty._pools["index"]]}
    assert dirty.generate_batch(
        [{"tokens": prompt, "max_new_tokens": NEW}])[0] == want


@pytest.mark.parametrize("sharing", [False, True],
                         ids=["unshared", "shared"])
def test_deep_passes_answer_as_chunks_of_16_do(alone, sharing):
    """Four prefill lanes give the family's sparse setting a narrow and a
    DEEP pass (2 x 32: index scores of 32 queries a lane, a threshold a
    query, the chunk kernel's mask) at 256 and 512 columns: a prompt of
    230 rows is deep from its first chunk, two that arrive behind it —
    on its own pages where prefixes are shared — wait their turn, and
    every answer is that of the engine held to two lanes of 16 (which is
    the reference's: the test above), with nothing compiled after the
    warm-up."""
    eng = _engine(params=alone._params, prefill_lanes=4,
                  prefix_sharing=sharing)
    assert eng._deep_prefill == (2, 32) and alone._deep_prefill is None
    assert eng._prefill_programs() == [
        (4, 16, 256), (2, 16, 256), (2, 32, 256), (2, 32, 512)]
    eng.warm_up()
    before = eng.stats()
    prompts = [[int(t) for t in TOKENS[:n]] for n in (230, 150, 40)]
    seqs = [eng.submit({"tokens": prompts[0], "max_new_tokens": NEW})]
    for _ in range(3):
        eng.step()
    assert seqs[0].pos == 96
    seqs += [eng.submit({"tokens": p, "max_new_tokens": NEW})
             for p in prompts[1:]]
    _drain(eng)
    st = eng.stats()
    # (a copy-on-write split's row copies are eager operations, compiled
    # at the first split of a process: not a pass's program)
    assert sharing or st["compiles_total"] == before["compiles_total"]
    assert st["prefix_hits"] == 2 * sharing
    assert st["prefix_tokens_shared"] == (96 + 39) * sharing
    # three alone, four beside the second prompt (or, where it started
    # on the first one's pages, two beside it and two alone again)
    assert st["prefill_deep_passes_total"] == 7
    assert st["prefill_passes_by_width"][512] == 0
    assert st["sparse_rows_selected_total"]["prefill"] > 0
    want = alone.generate_batch([{"tokens": p, "max_new_tokens": NEW}
                                 for p in prompts])
    assert [list(s.generated) for s in seqs] == want
    assert st["latent_pages_in_use"] == 0
