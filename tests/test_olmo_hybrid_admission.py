"""The hybrid linear-attention family through the three shapes of a
prefill pass (narrow_prefill_cases.py), and admission that waits on
PAGES: an engine whose `num_pages` is below max_batch x pages_per_seq,
as the cell's is — against the plain reference (seeded random weights,
small size, float32, CPU)."""

import dataclasses

import jax.numpy as jnp
import narrow_prefill_cases
import numpy as np
import pytest

from benchmarks import reference_olmo as ref
from ray_tpu.models.olmo_hybrid import OlmoHybridConfig
from ray_tpu.serve.llm import LLMEngine

CFG = dataclasses.replace(OlmoHybridConfig.tiny(), dtype=jnp.float32,
                          param_dtype=jnp.float32)
PAGE = 16
SIZES = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
         if "dtype" not in f.name}
ATOL = 2e-5    # float32 sums in another order (tests/test_olmo_hybrid_model.py)


@pytest.fixture(scope="module", autouse=True)
def short_reference():
    """The reference pads to 256 here, not to the chip's lengths."""
    was, ref.LENGTHS = ref.LENGTHS, (256, 512)
    yield
    ref.LENGTHS = was


def _drain(eng):
    while eng.step():
        pass
    eng.drain()


def _prompt(n, salt=0):
    return [int(t) for t in np.random.RandomState(100 + salt).randint(
        1, 256, n)]


def _assert_references(eng, prompts, outs, top2=None):
    """Every token is the reference's argmax given the engine's own
    earlier tokens; with the engine's logit trace, its two largest
    logits are the reference's."""
    refs = ref.teacher_forced(eng._params, prompts, outs, SIZES)
    for p, out, r in zip(prompts, outs, refs):
        assert out and out == r["top_id"], f"prompt of {len(p)}"
    return refs


class _NarrowKit:
    """This family's kit for `narrow_prefill_cases`: chunk 16 under a
    context of 384 gives the prefill pass the widths 64, 256 and 384;
    the state group follows the pass's lanes."""

    @staticmethod
    def make(max_len=384, **kw):
        return LLMEngine(
            dataclasses.replace(CFG, max_position_embeddings=max_len),
            seed=5, page_size=PAGE, max_batch=4, prefill_chunk=16, **kw)

    @staticmethod
    def make_one_width():
        return _NarrowKit.make(max_len=64)

    @staticmethod
    def prompt(n, salt=0):
        return _prompt(n, salt)

    check = staticmethod(narrow_prefill_cases.teacher_forced_check(
        ref, SIZES))


@pytest.mark.parametrize("case", narrow_prefill_cases.CASES,
                         ids=lambda case: case.__name__)
def test_narrow_prefill_pass(case):
    case(_NarrowKit)


# ------------------------------------------------ admission that waits on pages


def test_admission_waits_for_pages_and_gives_every_one_back():
    """An engine whose `num_pages` is below max_batch x pages_per_seq
    (the cell's: 4,097 pages for 32 lanes of 1,024): twelve requests of
    9 to 14 pages through 24 pages.  The head of the queue waits for
    pages to recycle, lanes stand empty meanwhile, every request is
    served IN ORDER of arrival, every token is the reference's, and
    every page and state slot comes back."""
    eng = LLMEngine(CFG, seed=5, page_size=PAGE, max_batch=4, num_pages=25)
    full = eng._groups["full"]
    assert full.num_pages == 25 < 1 + 4 * (CFG.max_seq_len // PAGE)
    lengths = (150, 200, 130, 180, 160, 140, 210, 135, 190, 170, 145, 205)
    reqs = [{"tokens": _prompt(n, 40 + i), "max_new_tokens": 6,
             "request_id": f"p{i}"} for i, n in enumerate(lengths)]
    seqs = [eng.submit(r) for r in reqs]
    waited = most = 0
    for _ in range(2000):
        more = eng.step()
        st = eng.stats()
        most = max(most, st["used_pages"])
        if st["queued"] and st["active"] < 4:
            waited += 1     # a lane is free and the queue's head waits
        if not more:
            break
    eng.drain()
    assert all(s.done for s in seqs)
    outs = [list(s.generated) for s in seqs]
    assert all(len(o) == 6 for o in outs)
    _assert_references(eng, [r["tokens"] for r in reqs], outs)
    assert waited > 0 and most <= 24
    st = eng.stats()
    assert st["used_pages"] == 0 and st["free_pages"] == 24
    assert sorted(full.free) == list(range(1, 25))
    assert st["state_slots_in_use"] == 0
    assert sorted(eng._groups["state"].free) == [1, 2, 3, 4]


def test_requests_are_admitted_in_order_while_pages_are_short():
    """Head-of-line: while the first waiting request does not fit, no
    later (shorter) one overtakes it."""
    eng = LLMEngine(CFG, seed=5, page_size=PAGE, max_batch=4, num_pages=25)
    order = []
    admit = eng._groups["full"].admit

    def spy(plan):
        order.append(plan[0])
        return admit(plan)

    eng._groups["full"].admit = spy
    lengths = (200, 180, 20, 190, 30, 170)
    seqs = [eng.submit({"tokens": _prompt(n, 60 + i), "max_new_tokens": 4})
            for i, n in enumerate(lengths)]
    _drain(eng)
    assert all(s.done for s in seqs)
    assert order == [-(-(n + 4) // PAGE) for n in lengths]
