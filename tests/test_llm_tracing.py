"""What the serving engine says of itself (serve/llm.py): the step's
phases and the requests' stages as cumulative counters in `stats()`,
the compile record of `ray_tpu.ops`, request spans under the caller's
trace, and `llm.*` spans on the profiler's clock.

Engine-level, no cluster: every test drives `step()` inline or runs
`run_loop` on a thread of its own.  The model is tiny and float32; its
widths are this file's own, so that the process-wide jit cache holds no
program of another test's engine when a test counts compiles.
"""

import os
import sys
import threading
import time

import jax.numpy as jnp
import pytest

from ray_tpu import ops
from ray_tpu._private import tracing
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve import llm
from ray_tpu.serve.llm import (_CPU_GROUP, _HOST_PHASES, _HOST_SPANS, _PHASES,
                               LLMEngine, _LLMCallable, _StepClock)

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # benchmarks/, for trace_reduce

MODEL = {"vocab_size": 64, "dim": 48, "n_layers": 2, "n_heads": 4,
         "n_kv_heads": 2, "hidden_dim": 96, "max_seq_len": 64}
CHUNK, LANES = 8, 2
_params = []


def _engine(**kw):
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("prefill_lanes", LANES)
    kw.setdefault("prefix_sharing", False)
    if _params:   # flax init costs seconds here: once for the file
        kw["params"] = _params[0]
    eng = LLMEngine(LlamaConfig(dtype=jnp.float32, **MODEL), **kw)
    _params[:1] = [eng._params]
    eng.warm_up()
    return eng


def _request(i, n_prompt=11, max_new=4):
    return {"tokens": [1 + (i + j) % 60 for j in range(n_prompt)],
            "max_new_tokens": max_new}


def _drain(eng, seqs, limit_s=120.0):
    deadline = time.monotonic() + limit_s
    while any(not s.done for s in seqs):
        assert time.monotonic() < deadline, "the engine did not finish"
        eng.step()


def _delta(after, before):
    """The change of every number of two `stats()`, nested groups too."""
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            out[k] = _delta(v, before[k])
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = v - before[k]
    return out


def test_phases_add_up_to_the_step_and_the_passes_keep_their_meaning():
    eng = _engine()
    assert set(eng.stats()["phase_secs"]) == set(_PHASES)
    before = eng.stats()
    _drain(eng, [eng.submit(_request(i)) for i in range(5)])
    d = _delta(eng.stats(), before)
    p = d["phase_secs"]
    assert all(v > 0 for v in p.values()), p
    assert sum(p.values()) == pytest.approx(d["step_secs"], rel=1e-9)
    # a pass's seconds are its four phases: this step's build and
    # dispatch, the wait for the step before and its emission
    for kind in ("decode", "prefill"):
        assert sum(p[f"{kind}_{part}"] for part in
                   ("build", "dispatch", "sync", "emit")) \
            == pytest.approx(d[f"{kind}_secs"], rel=1e-9)
    assert d["decode_steps"] > 0 and d["prefill_steps"] > 0
    # a step that finds nothing to do is a step too: all of it is `admit`
    idle = eng.stats()
    assert eng.step() is False
    d = _delta(eng.stats(), idle)
    assert d["steps"] == 0
    assert d["step_secs"] == pytest.approx(d["phase_secs"]["admit"])
    assert d["step_secs"] > 0
    # start-up: the weights, the pools, the warm-up's compiles
    assert set(eng.startup_secs) == {"params", "pools", "warm"}
    assert all(v > 0 for v in eng.stats()["startup_secs"].values())


def test_a_sync_span_names_the_step_it_waits_for():
    """The engine reads step n's outputs after it dispatched step n+1:
    `llm.prefill.sync` and `llm.decode.sync` inside `llm.step` n+1 carry
    `of` = n, and every pass dispatched is read back once."""
    eng = _engine()
    spans, real = [], eng._clock.span

    def spy(name, **args):
        spans.append((name, args))
        return real(name, **args)

    eng._clock.span = spy
    before = eng.stats()
    _drain(eng, [eng.submit(_request(i, max_new=6)) for i in range(3)])
    d = _delta(eng.stats(), before)
    step, waited = None, {"llm.prefill.sync": [], "llm.decode.sync": []}
    for name, args in spans:
        if name == "llm.step":
            step = args["n"]
        elif name in waited:
            assert args["of"] == step - 1, (name, args, step)
            waited[name].append(args["of"])
    assert len(waited["llm.decode.sync"]) == d["decode_steps"] > 0
    assert len(waited["llm.prefill.sync"]) == d["prefill_steps"] > 0
    for ofs in waited.values():
        assert ofs == sorted(set(ofs))


def test_request_and_work_counters_equal_what_was_sent():
    eng = _engine()   # two lanes
    before = eng.stats()
    n, n_prompt, max_new = 5, 11, 4
    seqs = [eng.submit(_request(i, n_prompt, max_new)) for i in range(n)]
    _drain(eng, seqs)
    d = _delta(eng.stats(), before)
    for key in ("submitted_total", "admitted_total", "first_tokens_total",
                "finished_total"):
        assert d[key] == n, key
    assert d["cancelled"] == 0
    # the third request was submitted behind a full batch and waited for
    # a lane; the counter is the sum of every request's wait
    waits = [s.admitted_at - s.submitted_at for s in seqs]
    assert waits[2] > waits[0] >= 0 and waits[2] > 1e-4
    assert d["queue_wait_secs_total"] == pytest.approx(sum(waits))
    assert d["prefill_wait_secs_total"] == pytest.approx(
        sum(s.first_token_at - s.admitted_at for s in seqs))
    # work: every prompt token prefilled once, in passes of lanes x chunk
    # slots; every token but a request's first came from a decode lane
    assert d["prefill_tokens_total"] == n * n_prompt
    assert d["prefill_slots_total"] == d["prefill_steps"] * LANES * CHUNK
    assert d["decode_lane_steps_total"] == n * (max_new - 1)
    assert 1.0 <= d["decode_lane_steps_total"] / d["decode_steps"] <= 2.0
    # a cancelled request ends, and is not finished
    s = eng.submit(_request(9, max_new=30))
    eng.step()
    assert eng.cancel(s.request_id)
    d = _delta(eng.stats(), before)
    assert d["submitted_total"] == n + 1 and d["finished_total"] == n
    assert d["cancelled"] == 1


def _clock_identities(d):
    """What holds between the clock's readings in any change `d` of
    `stats()`, to rounding: every instant is in one phase, the host's
    phases are the nine less the two waits plus `between`, a starved or
    off-CPU second is a second of the phase (of the stretch between two
    readings of the CPU clock) it is booked under."""
    p = d["phase_secs"]
    assert d["loop_secs"] == pytest.approx(
        sum(p.values()) + d["between_secs"] + d["park_secs"], rel=1e-9)
    assert d["loop_secs"] == pytest.approx(
        d["step_secs"] + d["between_secs"] + d["park_secs"], rel=1e-9)
    host = {**{k: p[k] for k in _HOST_PHASES if k in p},
            "between": d["between_secs"]}
    assert set(host) == set(_HOST_PHASES) == set(d["starved_secs"])
    stretch = dict.fromkeys(d["off_cpu_secs"], 0.0)
    for k, secs in host.items():
        stretch[_CPU_GROUP[k]] += secs
    assert set(stretch) == {"front", "prefill_emit", "decode_emit",
                            "between"}
    # (as far as the CPU clock was read: not the open step's last
    # stretch where another thread asks mid-step)
    assert d["host_wall_secs"] == pytest.approx(sum(host.values()))
    assert sum(d["starved_secs"].values()) \
        == pytest.approx(d["starved_secs_total"])
    assert 0.0 <= d["starved_secs_total"] <= d["loop_secs"]
    assert sum(d["off_cpu_secs"].values()) \
        == pytest.approx(d["host_off_cpu_secs"])
    assert d["host_cpu_secs"] + d["host_off_cpu_secs"] \
        == pytest.approx(d["host_wall_secs"])
    assert 0.0 <= d["host_cpu_secs"] <= d["host_wall_secs"]
    for k, secs in host.items():
        assert -1e-12 <= d["starved_secs"][k] <= secs + 1e-12, k
    for k, secs in stretch.items():
        assert -1e-12 <= d["off_cpu_secs"][k] <= secs + 1e-12, k
    assert set(d["host_secs"]) == set(_HOST_SPANS)


def test_no_instant_of_the_stepping_thread_is_unnamed_stepped_inline():
    t0 = time.perf_counter()
    eng = _engine()
    before = eng.stats()
    eng.generate_batch([_request(i) for i in range(5)])
    eng.generate_batch([_request(i) for i in range(2)])
    d = _delta(eng.stats(), before)
    wall = time.perf_counter() - t0
    _clock_identities(d)
    # nobody parks an engine stepped inline; the clock runs from the
    # engine's first step (its warm-up's) on, the callers' own code and
    # whatever lies between two calls `between`
    assert d["park_secs"] == 0.0 and d["between_secs"] > 0.0
    assert d["step_secs"] < d["loop_secs"] <= wall
    # the suspects inside the phases, by name: a part of its phase
    assert 0.0 < d["host_secs"]["plan"] <= d["phase_secs"]["admit"]
    assert 0.0 < d["host_secs"]["grid_count"] \
        <= d["phase_secs"]["decode_build"]
    assert d["host_secs"]["gauges"] > 0.0
    assert d["host_secs"]["window_arrays"] == 0.0   # one kind of layer


def test_no_instant_is_unnamed_under_a_pinned_loop():
    eng = _engine()
    before = eng.stats()
    loop = threading.Thread(target=eng.run_loop, daemon=True)
    loop.start()
    try:
        for _burst in range(2):
            seqs = [eng.submit(_request(i, max_new=6)) for i in range(3)]
            deadline = time.monotonic() + 120
            while any(not s.done for s in seqs):
                assert time.monotonic() < deadline
                time.sleep(0.005)
            time.sleep(0.06)   # longer than one park
    finally:
        eng.stop()
        loop.join(30)
    assert not loop.is_alive()
    d = _delta(eng.stats(), before)
    _clock_identities(d)
    assert d["park_secs"] > 0.05 and d["between_secs"] > 0.0
    # parked, the engine has nothing in flight: idle, not starved
    assert d["starved_secs_total"] <= d["loop_secs"] - d["park_secs"]


class _Ticks:
    """`time` for the clock alone: every reading of the wall clock is
    one second after the last; the thread's CPU clock stands where the
    test puts it, and its readings are counted."""

    def __init__(self):
        self.now = self.cpu = 0.0
        self.cpu_reads = 0

    def perf_counter(self):
        self.now += 1.0
        return self.now

    def thread_time(self):
        self.cpu_reads += 1
        return self.cpu


class _Pass:
    """A stand-in for a pass in flight: `ready` is what `is_ready()`
    answers, and every question is recorded."""

    def __init__(self, step, ready=False):
        self.step, self.out, self.ready, self.asked = step, self, ready, 0

    def is_ready(self):
        self.asked += 1
        return self.ready


def test_the_starvation_clock_counts_from_the_boundary_that_saw_it(
        monkeypatch):
    ticks = _Ticks()
    monkeypatch.setattr(llm, "time", ticks)
    flight = []
    clock = _StepClock(flight)

    def starved():
        got = {k: v for k, v in clock.starved_secs.items() if v}
        assert sum(got.values()) == clock.stats()["starved_secs_total"]
        assert clock.dry_in_dispatch == 0   # every one seen at a boundary
        return got, clock.chained_dry

    # an empty flight: nothing is asked, nothing counted
    clock.begin(0)
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    clock.dispatched()
    flight.append(_Pass(0))
    clock.end()                        # asked: a pass is in flight
    assert starved() == ({}, 0) and flight[-1].asked == 1
    assert clock.chained == 0          # onto nothing: idle, not chained
    # step 1: the pass in flight is done at the step's THIRD boundary
    # (the one that opens the dispatch): the dispatch's second, nothing
    # before
    clock.begin(1)                     # boundary 1: not ready
    clock.phase("decode_build")        # boundary 2: not ready
    flight[-1].ready = True
    clock.phase("decode_dispatch")     # boundary 3: ready
    clock.dispatched()                 # seen dry already: not asked again
    assert flight[-1].asked == 4
    flight.append(_Pass(1))
    clock.phase("decode_sync", of=0)   # the dispatch ends: counted
    assert starved() == ({"decode_dispatch": 1.0}, 1)
    # a boundary that opens a sync asks nothing (the wait tells)
    assert flight[-1].asked == 0
    flight.pop(0)
    flight[-1].ready = True            # done while the host waited
    clock.phase("decode_emit")         # seen here: emit, between, ...
    clock.end()
    clock.begin(2)
    clock.phase("prefill_build")
    clock.phase("prefill_dispatch")
    clock.dispatched()
    asked = flight[-1].asked
    assert starved() == ({"decode_dispatch": 1.0}, 2)   # not yet landed
    flight.append(_Pass(2))
    # ... up to and including the next dispatch, and no further: the
    # prefill pass is the newest now, and it is running
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    clock.dispatched()                 # chained onto the running prefill
    assert flight[-2].asked == asked   # seen dry: not asked again
    flight.append(_Pass(2))
    assert starved() == ({"decode_dispatch": 1.0, "decode_emit": 1.0,
                          "between": 1.0, "admit": 1.0,
                          "prefill_build": 1.0, "prefill_dispatch": 1.0}, 2)
    # nothing inside a sync: the newest pass is done before the waits
    # for the older ones, whose seconds are the device's, not the host's
    flight.pop(0)
    flight[-1].ready = True
    clock.phase("prefill_sync", of=1)
    flight.pop(0)
    clock.phase("prefill_emit")        # seen here
    clock.phase("decode_sync", of=2)   # a wait: not counted
    clock.phase("decode_emit")
    clock.end()
    clock.begin(3)
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    clock.dispatched()
    flight.append(_Pass(3))
    clock.end()
    got, steps = starved()
    assert steps == 3 and "decode_sync" not in clock.starved_secs
    assert clock.chained == 4
    assert got == {"decode_dispatch": 2.0, "decode_emit": 2.0,
                   "between": 2.0, "admit": 2.0, "prefill_build": 1.0,
                   "prefill_dispatch": 1.0, "prefill_emit": 1.0,
                   "decode_build": 1.0}
    # seen dry, but no dispatch follows (nothing left to do: the step
    # reads what is in flight and the engine idles): not starved
    for rec in flight:
        rec.ready = True
    clock.begin(4)                     # seen here
    clock.phase("decode_sync", of=2)
    del flight[:]
    clock.phase("decode_emit")
    clock.end()
    clock.phase("park")
    clock.phase("between")
    clock.begin(5)                     # a request came: nothing in flight
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    clock.dispatched()
    clock.end()
    assert starved() == (got, 3)
    # the upper bound's share: the one phase before each boundary that
    # saw it (behind a sync, whose opening asks nothing, the dispatch
    # before it too), booked when the dispatch landed — and nothing of
    # the stretch that ended with no dispatch (idle, not starved)
    assert {k: v for k, v in clock.starved_before_secs.items() if v} == {
        "decode_build": 1.0, "decode_sync": 1.0, "prefill_sync": 2.0}
    assert (clock.chained, clock.chained_dry) == (4, 3)
    # one reading of the wall clock a boundary, each a second here: the
    # phases and the time outside them add up to the loop's seconds
    st = clock.stats()
    assert st["loop_secs"] == sum(clock.secs.values()) == ticks.now - 1.0
    assert st["park_secs"] == 1.0 and st["between_secs"] == 6.0
    assert st["starved_secs_total"] == 12.0 <= st["loop_secs"]
    # a CPU clock that never moved: every host second was off the CPU
    assert st["host_cpu_secs"] == 0.0
    assert st["host_off_cpu_secs"] == st["host_wall_secs"] \
        == st["loop_secs"] - 1.0 - sum(
            v for k, v in clock.secs.items() if k.endswith("_sync"))


def test_a_dispatch_that_ends_dry_is_counted_and_bounds_the_idle_time(
        monkeypatch):
    """The device runs dry INSIDE a dispatch: no boundary sees it (the
    pass that was in flight when the dispatch opened is done when it
    ends, and the next boundary's newest pass is the new one), so the
    lower bound has nothing; `dispatched` asks once more, before the new
    pass joins the flight."""
    ticks = _Ticks()
    monkeypatch.setattr(llm, "time", ticks)
    flight = []
    clock = _StepClock(flight)
    marks, real = [], clock.span

    def spy(name, **args):
        if name == "llm.dry":
            marks.append((args, clock._key))
        return real(name, **args)

    clock.span = spy

    def counts():
        st = clock.stats()
        assert st["starved_secs_total"] == sum(st["starved_secs"].values())
        assert st["starved_before_secs_total"] \
            == sum(st["starved_before_secs"].values())
        return (st["chained_dispatches_total"],
                st["chained_dispatches_dry_total"],
                st["dry_in_dispatch_total"], st["starved_secs_total"],
                {k: v for k, v in st["starved_before_secs"].items() if v})

    # nothing in flight: the dispatch is neither chained nor dry
    clock.begin(0)
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    clock.dispatched()
    flight.append(_Pass(0))
    clock.end()
    assert counts() == (0, 0, 0, 0.0, {})
    # a pass in flight that outlasts the dispatch: chained, not dry
    clock.begin(1)
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    clock.dispatched()
    flight.append(_Pass(1))
    clock.phase("decode_sync", of=0)
    flight.pop(0)
    clock.phase("decode_emit")
    clock.end()
    assert counts() == (1, 0, 0, 0.0, {}) and not marks
    # the pass in flight ends inside the dispatch: every boundary found
    # it running, the dispatch's end finds it done
    clock.begin(2)
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    asked = flight[-1].asked
    flight[-1].ready = True
    clock.dispatched()                  # one more question, one clock read
    assert flight[-1].asked == asked + 1
    flight.append(_Pass(2))
    clock.phase("decode_sync", of=1)
    flight.pop(0)
    clock.phase("decode_emit")
    clock.end()
    # the dispatch's second so far is the upper bound's, of its two; the
    # lower bound is as it was
    assert counts() == (2, 1, 1, 0.0, {"decode_dispatch": 1.0})
    assert clock.secs["decode_dispatch"] == 1.0 + 1.0 + 2.0
    assert marks == [({"of": 1, "seen": "decode_dispatch"},
                      "decode_dispatch")]
    # seen inside a prefill dispatch, and the prefill pass itself is done
    # when the decode build opens: the rest of that dispatch is in doubt
    # too, each second once
    clock.begin(3)
    clock.phase("prefill_build")
    clock.phase("prefill_dispatch")
    flight[-1].ready = True
    clock.dispatched()
    flight.append(_Pass(3, ready=True))
    clock.phase("decode_build")         # sees the prefill pass done
    clock.phase("decode_dispatch")
    clock.dispatched()                  # dry on the way: counted, not asked
    flight.append(_Pass(3))
    clock.end()
    chained, dry, inside, lower, before = counts()
    assert (chained, dry, inside) == (4, 3, 2)
    assert before == {"decode_dispatch": 1.0, "prefill_dispatch": 2.0}
    assert clock.secs["prefill_dispatch"] == 2.0
    assert lower == 2.0 == clock.starved_secs["decode_build"] \
        + clock.starved_secs["decode_dispatch"]
    assert [m[0] for m in marks[1:]] == [
        {"of": 2, "seen": "prefill_dispatch"},
        {"of": 3, "seen": "decode_build"}]


class _Device:
    """A device for the clock's bounds to be held against: it runs the
    passes in order, each for a drawn number of clock reads, and knows
    when it had nothing to run."""

    def __init__(self, ticks, rs):
        self.ticks, self.rs = ticks, rs
        self.free_at = 0.0      # when the last pass enqueued ends
        self.idle = 0.0         # seconds it waited for a dispatch, truly

    def enqueue(self, chained: bool):
        now = self.ticks.now
        if chained and self.free_at < now:
            self.idle += now - self.free_at
        work = float(self.rs.choice([0.5, 1.5, 2.5, 4.5, 9.5]))
        self.free_at = max(self.free_at, now) + work
        return self.free_at


class _TimedPass(_Pass):
    """Done when the device's clock says so."""

    def __init__(self, step, ticks, done_at):
        super().__init__(step)
        self.ticks, self.done_at = ticks, done_at

    def is_ready(self):
        return self.ticks.now >= self.done_at


@pytest.mark.parametrize("seed", range(6))
def test_the_two_bounds_hold_the_devices_wait_over_a_random_walk(
        monkeypatch, seed):
    """Steps of random shape (a prefill pass, a decode pass, both, none;
    read-backs that lag by up to two steps; parks) over a device whose
    passes take random times: what the device truly waited for a chained
    dispatch lies between the clock's lower bound — less the tails of the
    dispatches it booked whole, the device having its pass from
    `dispatched` on — and its upper bound; no second is booked twice."""
    import numpy as np

    rs = np.random.RandomState(seed)
    ticks = _Ticks()
    monkeypatch.setattr(llm, "time", ticks)
    flight = []
    clock = _StepClock(flight)
    device = _Device(ticks, rs)
    tails = 0.0

    def reads(n):   # the host works: so many readings of the clock long
        for _ in range(n):
            ticks.perf_counter()

    def dispatch(kind, n):
        nonlocal tails
        clock.phase(f"{kind}_build")
        clock.phase(f"{kind}_dispatch")
        reads(rs.randint(0, 3))             # the jitted call
        seen_before = clock._dry
        clock.dispatched()
        landed = ticks.now
        rec = _TimedPass(n, ticks, device.enqueue(chained=bool(flight)))
        rec.kind = kind
        reads(rs.randint(0, 2))             # the host state it advances
        flight.append(rec)
        if seen_before:   # booked to the phase's end: the next reading
            tails += ticks.now + 1.0 - landed

    for n in range(300):
        clock.begin(n)
        for kind in ("prefill", "decode"):
            if rs.rand() < 0.6:
                dispatch(kind, n)
        lag = rs.randint(0, 3)
        while flight and flight[0].step <= n - lag:
            rec = flight.pop(0)
            clock.phase(rec.kind + "_sync", of=rec.step)
            ticks.now = max(ticks.now, rec.done_at)   # the wait
            clock.phase(rec.kind + "_emit")
        clock.end()
        if rs.rand() < 0.1 and not flight:
            clock.phase("park")
            clock.phase("between")
        st = clock.stats()
        lower = st["starved_secs_total"]
        upper = lower + st["starved_before_secs_total"]
        assert 0.0 <= lower <= upper <= st["loop_secs"]
        assert st["dry_in_dispatch_total"] \
            <= st["chained_dispatches_dry_total"] \
            <= st["chained_dispatches_total"]
        for k, secs in clock.secs.items():   # no second in both bounds
            assert clock.starved_secs.get(k, 0.0) \
                + clock.starved_before_secs[k] <= secs + 1e-9, k
        if not clock._dry:   # (a dry stretch is booked when it ends)
            assert lower - tails <= device.idle <= upper, \
                (n, lower, tails, device.idle, upper)
    assert clock.chained > 100 and clock.dry_in_dispatch > 5
    assert lower > 0 and upper > lower


def test_work_or_waiting_is_read_at_a_steps_ends_and_an_emits(monkeypatch):
    """The thread's CPU clock costs a system call, so the clock reads it
    at `begin`, at `end` and where an emit begins and ends: four
    stretches (`_CPU_GROUP`), each host second of which was on the CPU
    or off it."""
    ticks = _Ticks()
    monkeypatch.setattr(llm, "time", ticks)
    clock = _StepClock([])

    def burn(secs):   # the thread runs on the CPU for `secs`
        ticks.cpu += secs

    clock.begin(0)                      # admit
    reads = ticks.cpu_reads
    burn(0.75)
    clock.phase("prefill_build")
    clock.phase("prefill_dispatch")
    burn(0.5)
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    burn(0.25)
    clock.phase("prefill_sync", of=0)   # the wait burns nothing
    clock.phase("prefill_emit")         # read: `front` so far 1.5 of 5
    burn(1.0)
    clock.phase("decode_sync", of=0)    # read: the emit ran throughout
    clock.phase("decode_emit")          # read: nothing more of `front`
    burn(0.25)
    clock.end()                         # read
    burn(3.5)                           # a coarse tick: more than the
    clock.phase("park")                 # two seconds `between` lasted
    clock.phase("between")
    clock.begin(1)                      # read
    assert ticks.cpu_reads - reads == 5
    clock.phase("decode_build")
    clock.phase("decode_dispatch")
    clock.phase("decode_sync", of=1)
    clock.phase("decode_emit")          # read
    clock.end()                         # read: 3 a step without a prefill
    assert ticks.cpu_reads - reads == 7
    st = clock.stats()
    assert st["off_cpu_secs"] == {"front": 8.0 - 1.5, "prefill_emit": 0.0,
                                  "decode_emit": 2.0 - 0.25, "between": 0.0}
    assert st["host_wall_secs"] == 8.0 + 1.0 + 2.0 + 2.0
    assert st["host_off_cpu_secs"] == 6.5 + 1.75
    assert st["host_cpu_secs"] == st["host_wall_secs"] - 8.25
    # what the tick read beyond `between`'s two seconds is owed to its
    # next stretch: on the CPU for 1.5 of the next one
    clock.phase("park")
    clock.phase("between")
    clock.begin(2)
    clock.end()
    assert clock.stats()["off_cpu_secs"]["between"] == 0.5
    # another thread steps (an engine driven inline): its CPU clock is
    # its own, and what it read before is not `between`'s
    ticks.cpu = 1e6
    monkeypatch.setattr(llm.threading, "get_ident", lambda: -1)
    clock.begin(3)
    clock.end()
    assert clock.stats()["off_cpu_secs"]["between"] == 0.5 + 1.0


def test_one_turnaround_a_dispatching_step_behind_a_read_back():
    """`llm.turnaround` opens behind the last read-back of a step that
    leaves a pass on the device (`of`: the step that dispatched it, the
    one open) and ends inside the next step, at its first `_forward`."""
    eng = _engine()
    spans, real = [], eng._clock.span

    class Spy:
        def __init__(self, name, **args):
            self.row, self.inner = [name, args, None], real(name, **args)

        def __enter__(self):
            self.row[2] = step[0], "open"
            spans.append(tuple(self.row))
            return self.inner.__enter__()

        def __exit__(self, *exc):
            spans.append((self.row[0], self.row[1], (step[0], "close")))
            return self.inner.__exit__(*exc)

    step = [None]
    eng._clock.span = Spy
    real_begin = eng._clock.begin

    def begin(n):
        step[0] = n
        return real_begin(n)

    eng._clock.begin = begin
    before = eng.stats()
    _drain(eng, [eng.submit(_request(i, max_new=6)) for i in range(3)])
    eng.drain()
    d = _delta(eng.stats(), before)
    opened = [(args["of"], at) for name, args, (at, what) in spans
              if name == "llm.turnaround" and what == "open"]
    closed = [(args["of"], at) for name, args, (at, what) in spans
              if name == "llm.turnaround" and what == "close"]
    dispatching = sorted({at for name, _a, (at, what) in spans
                          if name.endswith(".dispatch") and what == "open"})
    # the step in flight is the one that is open when its predecessor
    # has been read; the turnaround ends one step later
    assert opened and all(of == at for of, at in opened)
    assert all(at == of + 1 for of, at in closed)
    # a dispatching step ends one where the step before it dispatched
    # and read (a burst's first step reads nothing, so its second ends
    # none); a burst's last read-back opens one that no dispatch ends:
    # it is dropped, not counted
    reading = {at for name, _a, (at, what) in spans
               if name.endswith(".sync") and what == "open"}
    assert len(closed) == len(opened)
    ended = [of + 1 for of, _at in opened if of + 1 in dispatching]
    assert ended == [n for n in dispatching
                     if n - 1 in dispatching and n - 1 in reading]
    assert d["turnarounds_total"] == len(ended) > 3
    assert len(opened) > len(ended)
    assert 0.0 < d["turnaround_secs"] < d["loop_secs"]


def _spans_of(fn):
    """The spans recorded while `fn` ran (the buffer is the process's)."""
    tracing.drain()
    fn()
    return tracing.drain()


def test_request_spans_ride_the_callers_trace():
    eng = _engine()
    caller = tracing.start_span("caller", parent=None)
    assert caller is not None, "tracing is on by default"

    def traced():
        token = tracing.activate(caller.context())
        try:
            seqs = [eng.submit(_request(i)) for i in range(3)]
        finally:
            tracing.restore(token)
        _drain(eng, seqs)
        return seqs

    seqs = []
    spans = _spans_of(lambda: seqs.extend(traced()))
    assert len(spans) == 9   # three a request, not one a token
    for seq in seqs:
        mine = [s for s in spans
                if s["attrs"]["request_id"] == seq.request_id]
        assert [s["name"] for s in mine] == ["llm.queue", "llm.prefill",
                                             "llm.decode"]
        for s in mine:
            assert s["trace_id"] == caller.trace_id
            assert s["parent_id"] == caller.span_id
            assert s["kind"] == tracing.KIND_INTERNAL
            assert s["attrs"]["prompt_tokens"] == 11
            assert s["attrs"]["tokens_generated"] == 4
            assert s["attrs"]["prefix_tokens_shared"] == 0
            assert s["attrs"]["first_step"] <= s["attrs"]["last_step"]
        # one stage ends where the next begins, on the wall clock
        q, p, dec = mine
        assert q["end"] == pytest.approx(p["start"], abs=1e-3)
        assert p["end"] == pytest.approx(dec["start"], abs=1e-3)
        assert abs(q["start"] - time.time()) < 300
        # the step numbers join a request to `llm.step` of a profile
        assert p["attrs"]["last_step"] == dec["attrs"]["first_step"]
        assert dec["attrs"]["last_step"] - dec["attrs"]["first_step"] == 3

    # sampled out, or no caller's trace at all: nothing is recorded
    def unsampled():
        with tracing.suppressed():
            s1 = eng.submit(_request(4))
        _drain(eng, [s1, eng.submit(_request(5))])

    assert _spans_of(unsampled) == []

    # a request cancelled in the queue has only the stage it reached
    def cancelled():
        token = tracing.activate(caller.context())
        try:
            held = [eng.submit(_request(i, max_new=20)) for i in (6, 7)]
            eng.step()
            waiting = eng.submit(_request(8))
        finally:
            tracing.restore(token)
        assert eng.cancel(waiting.request_id)
        for s in held:
            eng.cancel(s.request_id)

    spans = _spans_of(cancelled)
    by_rid = {}
    for s in spans:
        by_rid.setdefault(s["attrs"]["request_id"], []).append(s)
    shapes = sorted([s["name"] for s in rows] for rows in by_rid.values())
    assert shapes == [["llm.queue"],
                      ["llm.queue", "llm.prefill"],
                      ["llm.queue", "llm.prefill"]] or shapes == [
        ["llm.queue"], ["llm.queue", "llm.prefill", "llm.decode"],
        ["llm.queue", "llm.prefill", "llm.decode"]]
    for rows in by_rid.values():
        assert rows[-1]["status"] == "cancelled"
        assert all("status" not in s for s in rows[:-1])


def _blocked_lanes():
    """Two lanes, three requests: the third waits for a lane."""
    return _engine(), "lanes", 3


def _blocked_full():
    """Four lanes over 8 pages, requests of 3 pages: the third waits for
    pages with two lanes empty (an engine whose `num_pages` is below
    max_batch x pages_per_seq: tests/test_olmo_hybrid_admission.py)."""
    return _engine(max_batch=4, num_pages=9), "full", 3


def _blocked_state():
    """`StateSlots` has a slot a lane and its `fit` never refuses (the
    constructor takes no other number), so a state group that has no
    room is stood in for: the second of two slots is 'taken'."""
    import dataclasses

    from ray_tpu.models.granite import GraniteConfig

    eng = LLMEngine(dataclasses.replace(
        GraniteConfig.tiny(), dtype=jnp.float32, param_dtype=jnp.float32,
        max_position_embeddings=64), seed=5, page_size=8, max_batch=2,
        prefill_chunk=CHUNK, prefill_lanes=LANES)
    state = eng._groups["state"]
    state.fit = lambda total, tokens: total if len(state.free) > 1 else None
    return eng, "state", 2


@pytest.mark.parametrize("make", [_blocked_lanes, _blocked_full,
                                  _blocked_state],
                         ids=lambda f: f.__name__[9:])
def test_a_step_that_leaves_the_head_queued_says_what_refused_it(make):
    eng, reason, n = make()
    assert set(eng.stats()["admit_blocked_steps"]) \
        == {"lanes", *eng._groups}
    spans, real = [], eng._clock.span

    def spy(name, **args):
        if name == "llm.admit":
            spans.append(args)
        return real(name, **args)

    eng._clock.span = spy
    caller = tracing.start_span("caller", parent=None)
    before = eng.stats()
    seqs = []

    def traced():
        token = tracing.activate(caller.context())
        try:
            seqs.extend(eng.submit(_request(i, n_prompt=20, max_new=4))
                        for i in range(n))
        finally:
            tracing.restore(token)
        blocked = 0
        while any(not s.done for s in seqs):
            waiting = len(eng._queued)
            eng.step()
            # a step is blocked where requests are still queued behind
            # its admission (the queue only shrinks there)
            blocked += bool(eng._queued) and waiting > 0
        return blocked

    blocked = []
    recorded = _spans_of(lambda: blocked.append(traced()))
    d = _delta(eng.stats(), before)
    assert d["admit_blocked_steps_total"] == blocked[0] > 0
    assert d["admit_blocked_steps_total"] <= d["steps"]
    assert d["admit_blocked_steps"] == {
        k: blocked[0] if k == reason else 0
        for k in d["admit_blocked_steps"]}
    # the phase span says so: a blocked step's `admit` is cut in two at
    # the refusal, and the second carries the reason
    assert [a for a in spans if a] == [{"blocked": reason}] * blocked[0]
    # the request's own span: what it waited for as the queue's head
    queue = {s["attrs"]["request_id"]: s["attrs"]["blocked_by"]
             for s in recorded if s["name"] == "llm.queue"}
    assert [queue[s.request_id] for s in seqs] \
        == [""] * (n - 1) + [reason]
    assert all("blocked_by" not in s["attrs"] for s in recorded
               if s["name"] != "llm.queue")


def test_a_gauge_is_written_where_it_changed_and_reads_as_a_scan_would():
    """`_set_gauges` runs every step on the thread the device waits for:
    it sets a gauge only where the value differs from the last it wrote,
    and the page gauges come from counts the group keeps, not a scan."""
    eng = _engine(prefix_sharing=True, max_batch=4)
    m = eng.metrics()
    writes = []

    class Spy:
        def __init__(self, key):
            self.key, self.gauge = key, m[key]

        def set(self, value, tags=None):
            writes.append((self.key, (tags or {}).get("state"), value))
            self.gauge.set(value, tags=tags)

    for key in ("pages", "batch", "queue", "tps"):
        m[key] = Spy(key)
    shared = [1 + j % 50 for j in range(24)]   # three pages in common
    full = eng._groups["full"]
    seqs, most, steps = [], 0, 0
    while len(seqs) < 3 or any(not s.done for s in seqs):
        if steps in (0, 4, 5):   # the first one's pages are written by 4
            seqs.append(eng.submit({"tokens": shared + [60 + len(seqs)],
                                    "max_new_tokens": 12}))
        eng.step()
        steps += 1
        scan = sum(r > 1 for r in full.refs)
        assert full.gauges() == {"used": full.used(),
                                 "free": len(full.free), "shared": scan}
        most = max(most, scan)
    eng.step()   # an idle step publishes zeros
    assert most >= 3 and steps > 6
    last = {}
    for key, state, value in writes:
        assert last.get((key, state)) != value, "written twice"
        last[key, state] = value
    assert last.items() <= llm._gauged.items()
    # far fewer writes than six a step, and the last of each the truth
    assert len(writes) < 4 * steps
    # (the queue's depth was 0 at every step's end, as the warm-up left
    # it: never written again)
    assert last == {("pages", "used"): 0, ("pages", "shared"): 0,
                    ("pages", "free"): full.num_pages - 1,
                    ("batch", None): 0, ("tps", None): 0}
    assert m["queue"].gauge.render()[-1].endswith(" 0.0")
    pages = dict(line.rsplit(" ", 1) for line in m["pages"].gauge.render()
                 if not line.startswith("#"))
    assert {k.split('state="')[1].split('"')[0]: float(v)
            for k, v in pages.items()} == {
        "used": 0.0, "free": float(full.num_pages - 1), "shared": 0.0}


_polls = []   # one engine's two polls, for the six cases
NEW_METRICS = {
    "dispatch_dry_pct": (["chained_dispatches_dry_total"],
                         ["chained_dispatches_total"]),
    "device_starved_upper_pct": (["starved_secs_total",
                                  "starved_before_secs_total"],
                                 ["loop_secs"]),
    "admit_blocked_pct": (["admit_blocked_steps_total"], ["steps"]),
}


@pytest.mark.parametrize("suffix", ["tail", "load"])
@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_readings_reach_the_benchmark_as_data(name, suffix):
    """Six data files for the reader that is there (`stats_ratio`), found
    through BENCHMARK.json: a number from polls of an engine that has the
    keys, nothing (and no error) from a program that lacks them."""
    import json

    from benchmarks.spec import Spec

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric = f"{name}.{suffix}"
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    steady = [w["name"] for w in bench["workloads"]
              if w["name"].startswith("serve-") and "steady" in w["name"]]
    assert len(steady) == 8
    assert entry["workloads"] == (steady if suffix == "tail"
                                  else ["serve-chat-overload"])
    assert entry["moves"] == {
        ("tail", "dispatch_dry_pct"): "tpot_p95_ms",
        ("tail", "device_starved_upper_pct"): "tpot_p95_ms",
        ("tail", "admit_blocked_pct"): "ttft_p75_ms",
    }.get((suffix, name), "serve_tokens_per_s")
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == ("%", "lower", "program_counter", "engine")
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           metric + ".json")) as f:
        data = json.load(f)
    num, den = NEW_METRICS[name]
    assert data["reader"] == "stats_ratio"
    assert data["params"] == {"num": num, "den": den, "scale": 100.0}
    assert (data["unit"], data["layer"], data["moves"]) \
        == (entry["unit"], entry["layer"], entry["moves"])
    # two polls of a real engine, with work between them
    if not _polls:
        eng = _engine()
        first = eng.stats()
        _drain(eng, [eng.submit(_request(i, max_new=6)) for i in range(5)])
        _polls.append([first, eng.stats()])
    polls, first = _polls, _polls[0][0]
    cell = entry["workloads"][0]
    spec = Spec()   # of this one metric: the others' readers want a run
    spec.benchmark = dict(spec.benchmark, per_layer=[entry])
    got = spec.read_layer_metrics(cell, {"polls": polls})[metric]
    d = _delta(polls[0][1], first)
    want = 100.0 * sum(d[k] for k in num) / sum(d[k] for k in den)
    assert got["value"] == pytest.approx(want) and 0.0 <= want <= 100.0
    if name == "admit_blocked_pct":
        assert want > 0.0   # five requests through two lanes
    # the parent's program: no such key in its polls
    old = [[{k: v for k, v in row.items() if k not in num}
            for row in polls[0]]]
    assert spec.read_layer_metrics(cell, {"polls": old}) == {}


def test_compiles_are_counted_and_say_which_phase_and_step():
    eng = _engine()
    _drain(eng, [eng.submit(_request(i)) for i in range(3)])
    warm = eng.stats()
    assert warm["compiles_total"] > 0 and warm["compile_secs_total"] > 0
    for _ in range(2):   # the same traffic again compiles nothing
        _drain(eng, [eng.submit(_request(i)) for i in range(3)])
    assert eng.stats()["compiles_total"] == warm["compiles_total"]
    # a prefill pass of another shape is a new program
    eng.prefill_lanes = 1
    step = eng.stats()["steps"]
    _drain(eng, [eng.submit(_request(0))])
    after = eng.stats()
    assert after["compiles_total"] > warm["compiles_total"]
    assert after["compile_secs_total"] > warm["compile_secs_total"]
    report = eng.device_report()
    assert report["compiles_total"] >= after["compiles_total"]
    # (the decode pass behind it takes that pass's output, of another
    # length, as its token feed: a new program too, in ITS dispatch)
    new = report["recent_compiles"][
        warm["compiles_total"] - after["compiles_total"]:]
    first = new[0]
    assert first["phase"] == "prefill_dispatch" and first["step"] == step
    assert first["secs"] > 0
    assert {c["phase"] for c in new} == {"prefill_dispatch",
                                         "decode_dispatch"}
    # a thread that named nothing: its compiles carry no phase
    ops.note_phase(None)
    jnp.zeros((3, 5, 7)).block_until_ready()
    assert ops.device_report()["recent_compiles"][-1]["phase"] is None


def test_a_profile_holds_the_step_its_phases_and_the_park(tmp_path):
    from benchmarks import trace_reduce

    unattributed = "engine host, unattributed"
    replica = _LLMCallable(warm=False, model=LlamaConfig(
        dtype=jnp.float32, **MODEL), params=_engine()._params, page_size=8,
        max_batch=2, prefill_chunk=CHUNK, prefill_lanes=LANES)
    eng = replica._engine
    loop = threading.Thread(target=eng.run_loop, daemon=True)
    loop.start()

    def serve(n):
        seqs = [eng.submit(_request(i, max_new=6)) for i in range(n)]
        deadline = time.monotonic() + 120
        while any(not s.done for s in seqs):
            assert time.monotonic() < deadline
            time.sleep(0.005)

    try:
        serve(2)            # whatever compiles does so before the capture
        replica.profile_start(str(tmp_path))
        try:
            serve(3)
            # the idle loop parks between two bursts; shorter than one
            # park (50 ms), so that one span covers the device's gap
            time.sleep(0.03)
            serve(3)
            # a lull of several parks: no one of them covers the gap
            time.sleep(0.17)
            serve(2)
        finally:
            replica.profile_stop()
    finally:
        eng.stop()
        loop.join(30)
    assert not loop.is_alive()
    rows = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    names = {name for _s, _e, name in rows["host"]}
    assert {"llm.step", "llm.park", "llm.idle", "llm.between",
            "llm.turnaround", "llm.plan", "llm.gauges", "llm.grid_count"} \
        | set(_PHASES.values()) <= names
    # a phase lies inside a step: one clock, one thread
    steps = sorted((s, e) for s, e, n in rows["host"] if n == "llm.step")
    for s, e, n in rows["host"]:
        if n in _PHASES.values():
            assert any(s0 - 1e-6 <= s and e <= e0 + 1e-6
                       for s0, e0 in steps), n
    # the time outside a step lies outside every step, the host's named
    # work inside one
    for s, e, n in rows["host"]:
        if n in ("llm.between", "llm.park"):
            assert all(e <= s0 + 1e-6 or e0 <= s + 1e-6
                       for s0, e0 in steps), n
        elif n in _HOST_SPANS.values():
            assert any(s0 - 1e-6 <= s and e <= e0 + 1e-6
                       for s0, e0 in steps), n
    # a turnaround is NOT nested in a step: it covers the end of the
    # step that read back and the start of the next, and the profiler
    # keeps its interval as it was
    turns = [(s, e) for s, e, n in rows["host"] if n == "llm.turnaround"]
    across = [(s, e) for s, e in turns
              if any(s < e0 < e for _s0, e0 in steps)
              and any(s < s0 < e for s0, _e0 in steps)]
    assert len(across) >= len(turns) - 2 > 0, (len(across), len(turns))
    reduced = trace_reduce.reduce_events(**rows, unattributed=unattributed)
    gaps = dict(reduced["idle_gaps"])
    # the step in flight when the profile stops has no `llm.step` span in
    # the capture, so the device's gap under it has no name: one step's
    # worth at most, once in some 25 runs
    assert gaps.get(unattributed, 0.0) <= max(e - s for s, e in steps), gaps
    assert gaps["llm.park"] >= 0.02, gaps
    # the lull is cut into parks of 50 ms and the steps between them
    # that found nothing; `llm.idle` holds them all, and the gap is its
    parks = sorted((s, e) for s, e, n in rows["host"] if n == "llm.park")
    idles = [(s, e) for s, e, n in rows["host"] if n == "llm.idle"]
    lull = max(idles, key=lambda iv: iv[1] - iv[0])
    inside = [(s, e) for s, e in parks if lull[0] <= s and e <= lull[1]]
    assert len(inside) >= 3 and lull[1] - lull[0] >= 0.15
    assert any(lull[0] < s0 and e0 < lull[1] for s0, e0 in steps)
    assert gaps["llm.idle"] >= 0.1, gaps


def test_flax_names_the_model_parts_and_pallas_the_kernels():
    """Whoever opens a trace finds `embed`, `attn`, `mlp` and `lm_head`
    in an operation's name (flax scopes each module under its name), and
    the two kernels under names of their own."""
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaModel
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.paged_attention import paged_attention

    model = LlamaModel(LlamaConfig(dtype=jnp.float32, **MODEL))
    tokens = np.zeros((1, 8), np.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))
    text = jax.jit(model.apply).lower(shapes, tokens).as_text(
        debug_info=True)
    for scope in ("embed", "layer_1/attn", "layer_1/mlp", "lm_head"):
        assert f"/{scope}/" in text, scope
    q = jnp.zeros((2, 1, 4, 8), jnp.float32)
    pool = jnp.zeros((4 * 8, 2, 8), jnp.float32)
    text = jax.jit(lambda: paged_attention(
        q, pool, pool, jnp.zeros((2, 2), jnp.int32),
        jnp.ones((2,), jnp.int32), page_size=8)).lower().as_text(
        debug_info=True)
    assert "paged_attention_decode" in text
    x = jnp.zeros((1, 128, 4, 8), jnp.float32)
    text = jax.jit(lambda: flash_attention(x, x[:, :, :2], x[:, :, :2])
                   ).lower().as_text(debug_info=True)
    assert "flash_attention_fwd" in text
