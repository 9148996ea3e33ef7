"""Scale smoke tests — miniature versions of the reference's
scalability envelope (reference: release/benchmarks/README.md — queued
tasks, many actors, many objects), escalated toward the reference
numbers now that dispatch is batched (PR 8): 50k tasks queued at once,
a single 10k-ref get, 200 concurrent actors (the actor envelope runs
under the `slow` marker; tier-1 keeps a 24-actor version sized for its
wall budget)."""

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024,
                 # one 50k burst ahead of a grant means lease requests
                 # can queue behind ~2 minutes of worker spawns
                 _system_config={"worker_lease_timeout_ms": 240_000})
    try:
        yield ray_tpu
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow
def test_many_queued_tasks_drain(cluster):
    """Tens of thousands of tasks queued at once all complete
    (reference: '1M tasks queued on one node' scaled to the box) — the
    batched submit path (one push_tasks frame per lease pass, batched
    lease asks) is what makes this a queueing test instead of a
    frame-count test.  Moved behind `slow` with the 50k envelope (which
    subsumes it) when the LLM serving tests joined tier-1 and a serial
    run came close to its wall; tier-1 keeps the 10k-ref single-get and
    the 24-actor envelope below as its scale gates."""
    @ray_tpu.remote
    def unit(i):
        return i

    n = 10_000
    refs = [unit.remote(i) for i in range(n)]
    out = ray_tpu.get(refs, timeout=600)
    assert out == list(range(n))


@pytest.mark.slow
def test_many_queued_tasks_envelope(cluster):
    """The 50k-queued-tasks reference point (VERDICT weak #7)."""
    @ray_tpu.remote
    def unit(i):
        return i

    n = 50_000
    refs = [unit.remote(i) for i in range(n)]
    out = ray_tpu.get(refs, timeout=600)
    assert out == list(range(n))


def test_one_get_of_10k_refs(cluster):
    """One ray_tpu.get resolving 10k refs (reference: '10k plasma
    objects in one ray.get'): the vectorized driver get must resolve
    the batch in O(owners) frames, not O(refs)."""
    n = 10_000
    refs = [ray_tpu.put(i) for i in range(n)]
    assert ray_tpu.get(refs, timeout=300) == list(range(n))
    # the owner's reference table tracked every live ref through it
    summary = ray_tpu.api._worker().memory_summary(limit=20_000)
    assert summary["num_owned"] >= n


def test_many_actors(cluster):
    """Dozens of concurrent actors each serving calls — tier-1 sized
    (worker spawn on the CI box is ~0.7s/proc gated at
    worker_startup_parallelism; 24 fits the budget, the 200-actor
    envelope lives in test_many_actors_envelope below)."""
    @ray_tpu.remote
    class Cell:
        def __init__(self, base):
            self.base = base

        def bump(self, x):
            return self.base + x

    n = 24
    actors = [Cell.remote(i) for i in range(n)]
    refs = [a.bump.remote(j) for j in range(5) for a in actors]
    out = ray_tpu.get(refs, timeout=600)
    assert sum(out) == sum(i + j for j in range(5) for i in range(n))
    for a in actors:
        ray_tpu.kill(a)


@pytest.mark.slow
def test_many_actors_envelope(cluster):
    """The 200-actor reference envelope (reference: many_actors).
    Worker spawn dominates (~135s on the 2-CPU box with the spawn gate
    at parallelism 4), so this runs under the slow marker."""
    @ray_tpu.remote
    class Cell:
        def __init__(self, base):
            self.base = base

        def bump(self, x):
            return self.base + x

    n = 200
    actors = [Cell.remote(i) for i in range(n)]
    refs = [a.bump.remote(1) for a in actors]
    out = ray_tpu.get(refs, timeout=600)
    assert sum(out) == sum(i + 1 for i in range(n))
    for a in actors:
        ray_tpu.kill(a)


def test_deep_nested_submission(cluster):
    """Tasks submitting tasks several levels deep (owner chains,
    borrowed refs) complete without deadlock."""
    @ray_tpu.remote
    def descend(depth):
        if depth == 0:
            return 1
        return 1 + ray_tpu.get(descend.remote(depth - 1), timeout=120)

    assert ray_tpu.get(descend.remote(6), timeout=300) == 7


def test_async_task_put_and_nested_get(cluster):
    """An async task body (running on the shared loop thread) can put
    objects (unique IDs via the per-coroutine exec shadow) and block on
    nested tasks (the blocked-worker release still fires)."""
    @ray_tpu.remote
    def child(x):
        return x + 1

    @ray_tpu.remote
    async def parent(i):
        import asyncio as _a

        await _a.sleep(0.01)
        ref = ray_tpu.put({"i": i})            # put from a coroutine
        nested = ray_tpu.get(child.remote(i), timeout=120)
        return ray_tpu.get(ref, timeout=30)["i"], nested

    out = ray_tpu.get([parent.remote(i) for i in range(6)], timeout=300)
    assert out == [(i, i + 1) for i in range(6)]
