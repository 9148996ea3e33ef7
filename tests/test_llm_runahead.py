"""The serving engine one step ahead of its read-backs (serve/llm.py,
`LLMEngine.step`): step n+1 is dispatched before step n's tokens are
read, a decode pass takes its input tokens from the previous step's
outputs on the device, and what the host learns a step late (an `eos`,
a cancel, an expiry) costs one dropped lane-step and no wrong token.

Engine-level, inline, a tiny float32 model: greedy tokens are held to
the no-cache forward, token for token.  (The second family's case, window
pages given back under run-ahead, is in tests/test_laguna_engine.py,
beside the programs it shares.)
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private.errors import DeadlineExceededError
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.llm import LLMEngine

MODEL = {"vocab_size": 64, "dim": 32, "n_layers": 2, "n_heads": 4,
         "n_kv_heads": 2, "hidden_dim": 64, "max_seq_len": 64}
_params = []


def _engine(**kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 33)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefill_lanes", 2)
    if _params:   # flax init costs seconds here: once for the file
        kw.setdefault("params", _params[0])
    eng = LLMEngine(LlamaConfig(dtype=jnp.float32, **MODEL), **kw)
    _params[:1] = [eng._params]
    return eng


def _prompt(n, salt=0):
    rs = np.random.RandomState(100 + 13 * n + salt)
    return [int(t) for t in rs.randint(1, 64, n)]


def _assert_greedy(eng, prompt, generated):
    """ONE no-cache forward over prompt + generated: every generated
    token is the argmax at the position before it."""
    lg = np.asarray(eng._model.apply(
        {"params": eng._params},
        np.array([list(prompt) + list(generated)], np.int32))[0])
    want = [int(np.argmax(lg[len(prompt) + j - 1]))
            for j in range(len(generated))]
    assert list(generated) == want


def _run(eng, rounds=400):
    for _ in range(rounds):
        if not eng.step():
            return
    raise AssertionError("the engine did not go idle")


def _delta(eng, before, *keys):
    after = eng.stats()
    return [after[k] - before[k] for k in keys]


def test_under_churn_every_token_is_the_no_cache_forwards():
    """Staggered admissions, prompts of 1 to 3 chunks, different
    `max_new`, four lanes for nine requests: lanes move as sequences end
    (`src` carries the move), a prompt's last chunk feeds the next
    step's decode lane, and most decode passes run ahead."""
    eng = _engine()
    eng.warm_up()
    before = eng.stats()
    plan = [(3, 9), (8, 2), (17, 12), (24, 5), (9, 1), (16, 7), (5, 14),
            (20, 3), (11, 6)]
    seqs = []
    for i, (n_prompt, max_new) in enumerate(plan):
        seqs.append(eng.submit({"tokens": _prompt(n_prompt, i),
                                "max_new_tokens": max_new}))
        for _ in range(1 + i % 3):
            eng.step()
    _run(eng)
    for seq, (n_prompt, max_new) in zip(seqs, plan):
        assert seq.done and len(seq.generated) == max_new
        _assert_greedy(eng, seq.prompt, seq.generated)
    steps, ahead, lanes, wasted = _delta(
        eng, before, "decode_steps", "runahead_decode_steps_total",
        "decode_lane_steps_total", "decode_lane_steps_wasted_total")
    assert lanes == sum(max_new - 1 for _n, max_new in plan)
    assert wasted == 0    # every request ended by its count
    assert ahead / steps > 0.9
    assert eng.stats()["used_pages"] == 0 and not eng._flight


def test_an_eos_costs_one_lane_step_and_its_pages_serve_the_next():
    eng = _engine(num_pages=9)   # 8 pages: one 64-token reservation
    prompt = _prompt(6)
    free = eng.generate_batch([{"tokens": prompt, "max_new_tokens": 20}])[0]
    # an `eos` the sequence meets mid-stream, and not before
    k = next(i for i in range(3, 20) if free[i] not in free[:i])
    before = eng.stats()
    out = eng.generate_batch([{"tokens": prompt, "max_new_tokens": 58,
                               "eos": free[k]}])[0]
    assert out == free[:k + 1]
    lanes, wasted = _delta(eng, before, "decode_lane_steps_total",
                           "decode_lane_steps_wasted_total")
    # the lane-step behind the eos was in flight when it was read
    assert (lanes, wasted) == (k + 1, 1)
    st = eng.stats()
    assert st["used_pages"] == 0 and st["free_pages"] == 8
    assert not eng._flight
    # a sequence that takes every page, the stale write's too
    other = _prompt(9, salt=5)
    out = eng.generate_batch([{"tokens": other, "max_new_tokens": 50}])[0]
    _assert_greedy(eng, other, out)


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_sequence_ended_from_outside_with_a_lane_step_in_flight(how):
    """The ended sequence gets no further token, its lane-step in flight
    is counted as wasted, its pages are free at once, and the sequence
    beside it decodes on, onto the freed pages too."""
    eng = _engine()
    stays = eng.submit({"tokens": _prompt(10), "max_new_tokens": 30})
    goes = eng.submit({"tokens": _prompt(7, 1), "max_new_tokens": 30})
    for _ in range(6):
        eng.step()
    assert goes.ahead == 1 and len(goes.generated) >= 2
    had = list(goes.generated)
    before = eng.stats()
    if how == "cancel":
        assert eng.cancel(goes.request_id)
    else:
        goes.deadline = time.time() - 1.0   # the sweep finds it expired
        eng.step()
        assert isinstance(goes.error, DeadlineExceededError)
    assert goes.done and goes.cancelled
    assert eng.stats()["used_pages"] == -(-40 // 8)   # `stays` alone
    late = eng.submit({"tokens": _prompt(12, 2), "max_new_tokens": 20})
    _run(eng)
    assert goes.generated == had and goes.ahead == 0
    assert _delta(eng, before, "decode_lane_steps_wasted_total") == [1]
    for seq in (stays, late):
        assert seq.done and len(seq.generated) == seq.max_new
        _assert_greedy(eng, seq.prompt, seq.generated)
    _assert_greedy(eng, goes.prompt, goes.generated)
    assert eng.stats()["used_pages"] == 0


def test_save_restore_and_stop_with_a_step_in_flight_lose_no_token():
    eng = _engine()
    prompt, n = _prompt(11), 16
    seq = eng.submit({"tokens": prompt, "max_new_tokens": n,
                      "request_id": "kept"})
    for _ in range(7):
        eng.step()
    assert eng._flight and seq.ahead == 1
    dispatched = len(seq.generated) + seq.ahead
    snap = eng.save_state()   # inline: drains first
    assert not eng._flight and seq.ahead == 0
    assert len(snap["seqs"][0]["generated"]) == dispatched
    fresh = _engine()
    fresh.restore_state(snap)
    _run(fresh)
    restored = fresh._by_rid["kept"]
    assert restored.done and len(restored.generated) == n
    _assert_greedy(fresh, prompt, restored.generated)
    # the engine that was saved goes on, and is stopped mid-stream
    for _ in range(3):
        eng.step()
    assert eng._flight
    eng.stop()
    assert not eng._flight and seq.ahead == 0
    assert len(seq.generated) == seq.pos - len(prompt) + 1
    assert seq.generated == restored.generated[:len(seq.generated)]


def test_a_stopped_loop_reads_what_it_had_in_flight():
    eng = _engine()
    eng.warm_up()
    loop = threading.Thread(target=eng.run_loop, daemon=True)
    loop.start()
    prompt = _prompt(5)
    seq = eng.submit({"tokens": prompt, "max_new_tokens": 50})
    deadline = time.monotonic() + 120
    while len(seq.generated) < 5:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    eng.stop()
    loop.join(60)
    assert not loop.is_alive()
    assert not eng._flight and seq.ahead == 0
    assert len(seq.generated) == seq.pos - len(prompt) + 1
    _assert_greedy(eng, prompt, seq.generated)


def test_sampling_feeds_the_same_tokens_in_the_same_order_of_splits():
    """`temperature > 0`: one `jax.random.split` a pass in dispatch
    order, as the synchronous engine made them, and the sampled token
    fed on the device is the one the host would have fed: an engine
    drained after every step (every token read before the next pass is
    built, so fed from the host) samples the same streams."""
    reqs = [{"tokens": _prompt(n, 3), "max_new_tokens": m}
            for n, m in ((5, 12), (13, 9), (20, 6))]

    def sampled(drained):
        eng = _engine(temperature=0.8, top_k=8, seed=11)
        seqs = [eng.submit(dict(r)) for r in reqs]
        while any(not s.done for s in seqs):
            eng.step()
            if drained:
                eng.drain()
        eng.drain()
        assert (eng.stats()["runahead_decode_steps_total"] == 0) == drained
        return [list(s.generated) for s in seqs]

    ahead = sampled(drained=False)
    assert ahead == sampled(drained=True)
    greedy = _engine().generate_batch([dict(r) for r in reqs])
    assert ahead != greedy   # it sampled


def test_runahead_engages_under_load_and_not_for_a_lone_token():
    eng = _engine()
    eng.warm_up()
    before = eng.stats()
    eng.generate_batch([{"tokens": _prompt(4), "max_new_tokens": 1}])
    assert _delta(eng, before, "decode_steps",
                  "runahead_decode_steps_total") == [0, 0]
    before = eng.stats()
    eng.generate_batch([{"tokens": _prompt(6, i), "max_new_tokens": 40}
                        for i in range(4)])
    steps, ahead = _delta(eng, before, "decode_steps",
                          "runahead_decode_steps_total")
    assert steps == 40 and ahead / steps > 0.9
    # a lone request's first decode pass follows its prefill pass, which
    # is unread: only a pass behind a drained engine does not run ahead
    seq = eng.submit({"tokens": _prompt(3), "max_new_tokens": 3})
    eng.step()
    eng.drain()
    before = eng.stats()
    eng.step()
    assert _delta(eng, before, "decode_steps",
                  "runahead_decode_steps_total") == [1, 0]
    _run(eng)
    assert len(seq.generated) == 3


class _SpyNumpy:
    """numpy, but `asarray` of a device array is recorded."""

    def __init__(self, np_module, log):
        self._np, self._log = np_module, log

    def __getattr__(self, name):
        return getattr(self._np, name)

    def asarray(self, x, *args, **kw):
        if not isinstance(x, (self._np.ndarray, list, tuple)):
            self._log.append(("read", x))
        return self._np.asarray(x, *args, **kw)


def test_no_host_read_of_the_token_operand_between_two_dispatches():
    """A spy on the engine's `np.asarray` and on `_forward`: the arrays
    a decode pass takes as its token operand are the previous step's
    outputs themselves, and the host reads them only after that pass is
    dispatched."""
    eng = _engine()
    eng.warm_up()
    log = []
    eng._np = _SpyNumpy(np, log)
    forward = eng._forward

    def spy(*args, feed=None, **kw):
        out = forward(*args, feed=feed, **kw)
        log.append(("dispatch", feed and feed[1], out[0]))
        return out

    eng._forward = spy
    eng.generate_batch([{"tokens": _prompt(5, i), "max_new_tokens": 10}
                        for i in range(3)])
    decodes = [i for i, e in enumerate(log)
               if e[0] == "dispatch" and e[1] is not None]
    assert len(decodes) == 10
    for first, second in zip(decodes, decodes[1:]):
        out = log[first][2]
        operand = log[second][1]
        assert operand[0] is out    # the device array itself
        read = [e[1] for e in log[first:second] if e[0] == "read"]
        assert all(r is not out for r in read)
        # ... and it IS read, once, right after the second dispatch
        later = [e[1] for e in log[second:] if e[0] == "read"]
        assert sum(r is out for r in later) == 1
    # what was read between two decode dispatches is the step before's
    reads = [e[1] for e in log if e[0] == "read"]
    assert len(reads) == len({id(r) for r in reads})
