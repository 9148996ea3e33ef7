"""End-to-end deadline propagation + enforcement (ISSUE 14 tentpole).

Unit coverage for every enforcement site raising the typed
DeadlineExceededError — queued (owner pump, agent lease queue), running
(owner deadline sweep + cooperative cancel), get (ambient budget) —
plus nested ``.remote()`` propagation, the ingress-header parser, and
the jittered rpc reconnect backoff satellite.  The fourth site
(LLM admission) lives with the engine tests in test_serve_llm.py.
"""

import os
import random
import time

import pytest

import ray_tpu
from ray_tpu._private import deadlines


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=2, object_store_memory=64 * 1024 * 1024)
    try:
        # both workers up before any test's clock starts: under the whole
        # run's load a worker's start alone can outlast a 0.5 s budget,
        # and the task then expires queued, not running
        ray_tpu.get([_sleep.remote(0.2) for _ in range(2)], timeout=60)
        yield ray_tpu
    finally:
        ray_tpu.shutdown()


@ray_tpu.remote
def _sleep(s):
    time.sleep(s)
    return "done"


def test_running_task_fails_at_deadline(cluster):
    """A task mid-execution past its budget resolves with the typed
    error AT the deadline (the sweep resolves it owner-side, then
    cancels the worker) — the caller's get() does not wait out the
    task's natural 5s runtime."""
    t0 = time.monotonic()
    with pytest.raises(ray_tpu.DeadlineExceededError) as ei:
        ray_tpu.get(_sleep.options(timeout_s=0.5).remote(5), timeout=30)
    assert time.monotonic() - t0 < 3.0
    assert ei.value.where == "running"


def test_queued_task_fails_fast_without_running(cluster, tmp_path):
    """A task expiring while queued behind busy workers fails with
    where=queued and is NEVER dispatched (no side effects)."""
    marker = str(tmp_path / "ran")

    @ray_tpu.remote
    def doomed(path):
        open(path, "w").write("ran")
        return "ran"

    blockers = [_sleep.remote(1.5) for _ in range(2)]  # both CPUs busy
    time.sleep(0.3)  # blockers actually running
    t0 = time.monotonic()
    with pytest.raises(ray_tpu.DeadlineExceededError) as ei:
        ray_tpu.get(doomed.options(timeout_s=0.4).remote(marker),
                    timeout=30)
    assert time.monotonic() - t0 < 2.0  # failed FAST, not at blocker end
    assert ei.value.where == "queued"
    assert ray_tpu.get(blockers, timeout=60) == ["done", "done"]
    time.sleep(0.2)
    assert not os.path.exists(marker), "expired task was dispatched"


def test_nested_remote_inherits_deadline(cluster):
    """spec.deadline propagates through nested .remote() via the
    contextvar, the way trace context does: the inner task sees the
    OUTER caller's absolute deadline."""
    @ray_tpu.remote
    def inner_probe():
        return deadlines.current_deadline()

    @ray_tpu.remote
    def outer():
        return ray_tpu.get(inner_probe.remote(), timeout=30)

    expect = time.time() + 5.0
    got = ray_tpu.get(outer.options(timeout_s=5.0).remote(), timeout=30)
    assert got is not None and abs(got - expect) < 1.5, (got, expect)


def test_nested_get_spends_remaining_budget(cluster):
    """A get() inside a deadlined task is bounded by the ambient
    budget: the whole tree resolves at the outer deadline with the
    typed error (surfaced either by the inner get or the owner
    sweep, whichever wins the race)."""
    @ray_tpu.remote
    def hang_forever():
        time.sleep(30)

    @ray_tpu.remote
    def outer_waits():
        return ray_tpu.get(hang_forever.remote())

    t0 = time.monotonic()
    with pytest.raises((ray_tpu.DeadlineExceededError,
                        ray_tpu.RayTaskError)) as ei:
        ray_tpu.get(outer_waits.options(timeout_s=0.7).remote(),
                    timeout=30)
    assert time.monotonic() - t0 < 5.0
    e = ei.value
    cause = getattr(e, "cause", None)
    assert isinstance(e, ray_tpu.DeadlineExceededError) \
        or isinstance(cause, ray_tpu.DeadlineExceededError), (e, cause)


def test_driver_side_ambient_deadline_bounds_get(cluster):
    """get() with an active ambient deadline spends only the remaining
    budget — the 'get' enforcement site."""
    ref = _sleep.remote(10)  # will not finish inside the window
    token = deadlines.activate(time.time() + 0.4)
    t0 = time.monotonic()
    try:
        with pytest.raises(ray_tpu.DeadlineExceededError) as ei:
            ray_tpu.get(ref, timeout=30)
    finally:
        deadlines.restore(token)
    assert time.monotonic() - t0 < 2.0
    assert ei.value.where == "get"
    ray_tpu.cancel(ref, force=True)


def test_agent_drops_expired_lease_queue_entry(cluster):
    """Agent-side enforcement: a queued lease request whose spec
    deadline passed is dropped from the FIFO and the owner notified
    with the typed error reply — it never camps on the agent queue
    until the generic lease timeout."""
    from ray_tpu._private.ids import JobID, TaskID
    from ray_tpu._private.task_spec import TaskSpec

    w = ray_tpu.api._worker()
    blockers = [_sleep.remote(1.2) for _ in range(2)]  # exhaust CPUs
    time.sleep(0.3)
    spec = TaskSpec(
        task_id=TaskID.for_normal_task(JobID.from_hex(w.job_id)).hex(),
        job_id=w.job_id, function_id="f" * 8,
        resources={"CPU": 1}, owner_addr=w.address,
        caller_id=w.worker_id, deadline=time.time() - 1.0)
    t0 = time.monotonic()
    reply = w.agent.call("request_lease", spec=spec.to_wire(), timeout=30)
    assert reply.get("error") == "deadline exceeded", reply
    assert time.monotonic() - t0 < 2.0  # dropped, not lease-timeout'd
    assert ray_tpu.get(blockers, timeout=60) == ["done", "done"]


def test_actor_method_timeout(cluster):
    """.options(timeout_s=...) on actor method calls: an expired call
    resolves with the typed error while the actor survives."""
    @ray_tpu.remote
    class Slowpoke:
        def work(self, s):
            time.sleep(s)
            return "ok"

    a = Slowpoke.remote()
    assert ray_tpu.get(a.work.remote(0.01), timeout=30) == "ok"
    with pytest.raises(ray_tpu.DeadlineExceededError):
        ray_tpu.get(a.work.options(timeout_s=0.3).remote(5), timeout=30)
    # note: the force-cancel path may restart the worker; the actor
    # handle must still answer afterwards (max_restarts=0 actors die
    # with their worker — so assert only that undeadlined calls on a
    # FRESH actor are unaffected by the machinery)
    b = Slowpoke.remote()
    assert ray_tpu.get(b.work.remote(0.01), timeout=60) == "ok"


def test_deadline_metric_counts_sites(cluster):
    from ray_tpu._private.metrics import deadline_metrics

    c = deadline_metrics()
    before = dict(c._values)
    with pytest.raises(ray_tpu.DeadlineExceededError):
        ray_tpu.get(_sleep.options(timeout_s=0.2).remote(5), timeout=30)
    assert sum(c._values.values()) > sum(before.values())


# ----------------------------------------------------- header + helpers


def test_deadline_header_parse():
    now_ms = time.time() * 1000.0
    got = deadlines.from_header(str(now_ms + 5000))
    assert got is not None and abs(got - (now_ms / 1000.0 + 5.0)) < 0.01
    # malformed / absent / non-positive values are ignored, never errors
    for bad in (None, "", "abc", "-5", "0", object()):
        assert deadlines.from_header(bad) is None


def test_effective_deadline_tighter_wins():
    token = deadlines.activate(time.time() + 10.0)
    try:
        tight = deadlines.effective_deadline(1.0)
        assert tight is not None and tight - time.time() < 1.5
        loose = deadlines.effective_deadline(60.0)
        assert loose is not None and loose - time.time() < 11.0
    finally:
        deadlines.restore(token)
    assert deadlines.effective_deadline(None) is None


# ------------------------------------------ rpc reconnect backoff (jitter)


def test_backoff_schedule_exponential_jittered_capped():
    from ray_tpu._private.rpc import backoff_delays

    rng = random.Random(42)
    delays = [next(d) for d in [backoff_delays(0.05, 1.0, rng)]
              for _ in range(12)]
    # each draw sits in [ceiling/2, ceiling] with the ceiling doubling
    # 0.05 -> 0.1 -> ... -> capped at 1.0
    ceiling = 0.05
    for d in delays:
        assert ceiling / 2 - 1e-9 <= d <= ceiling + 1e-9, (d, ceiling)
        ceiling = min(ceiling * 2, 1.0)
    # capped: the tail never exceeds the cap but keeps jittering
    tail = delays[-4:]
    assert all(0.5 <= d <= 1.0 for d in tail), tail
    assert len(set(tail)) > 1, "no jitter at the cap"
    # deterministic per seed, different across seeds (the de-sync)
    a = [next(g) for g in [backoff_delays(rng=random.Random(7))]
         for _ in range(6)]
    b = [next(g) for g in [backoff_delays(rng=random.Random(7))]
         for _ in range(6)]
    c = [next(g) for g in [backoff_delays(rng=random.Random(8))]
         for _ in range(6)]
    assert a == b and a != c


# --------------------------------------- conftest module-budget tripwire


def test_module_budget_violation_detector():
    from conftest import _module_budget_violations

    durations = {"tests/test_a.py": 10.0, "tests/test_b.py": 50.0,
                 "tests/test_c.py": 45.0}
    over = _module_budget_violations(durations, budget=45.0)
    assert over == [("tests/test_b.py", 50.0)]
    assert _module_budget_violations({"m": 1.0}) == []
