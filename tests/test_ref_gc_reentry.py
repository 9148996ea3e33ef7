"""ObjectRef.__del__ can run inside a MemoryStore / ReferenceCounter
critical section: a cycle collection fires on whichever thread enters a
Python function, and the release it triggers takes the same locks.  No
cluster: a store/counter pair wired as CoreWorker wires them."""

import gc
import threading

import pytest

from ray_tpu._private import memory_store, reference_count
from ray_tpu._private.memory_store import MemoryStore
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.reference_count import ReferenceCounter


class _Worker:
    """The slice of CoreWorker on the ObjectRef.__del__ path."""

    def __init__(self):
        self.memory = MemoryStore()
        self.rc = ReferenceCounter(on_release=self.memory.evict)

    def register_local_ref(self, ref):
        self.rc.add_local(ref.oid, True)

    def unregister_local_ref(self, ref):
        self.rc.remove_local(ref.oid)


def _collect_inside(monkeypatch, cls):
    """Make constructing `cls` run a collection, as an allocation may."""
    init = cls.__init__

    def init_then_collect(self, *a, **kw):
        init(self, *a, **kw)
        gc.collect()

    monkeypatch.setattr(cls, "__init__", init_then_collect)


def _garbage_ref(w, oid):
    """An owned ref, resolved in the store, reachable only from a cycle."""
    ref = ObjectRef(oid)
    ref._worker = w
    w.register_local_ref(ref)
    w.memory.set_value(oid, 1)
    cycle = [ref]
    cycle.append(cycle)


@pytest.mark.parametrize("trigger", ["memory_store_entry", "refcount_ref"])
def test_collection_inside_constructor_does_not_deadlock(monkeypatch, trigger):
    w = _Worker()
    gc.disable()
    try:
        _garbage_ref(w, "a" * 32)
        if trigger == "memory_store_entry":
            _collect_inside(monkeypatch, memory_store._Entry)
            op = lambda: w.memory.ensure("b" * 32)
        else:
            _collect_inside(monkeypatch, reference_count._Ref)
            op = lambda: w.rc.add_local("b" * 32, True)
        t = threading.Thread(target=op, daemon=True)
        t.start()
        t.join(1.0)
        assert not t.is_alive(), "self-deadlock: __del__ re-entered a held lock"
    finally:
        gc.enable()
    assert not w.memory.known("a" * 32)   # the collected ref was released
    assert w.rc.is_freed("a" * 32)
    assert w.memory.known("b" * 32) or w.rc.count("b" * 32) == 1
