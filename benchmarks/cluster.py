"""Bringing the cluster up and down around one run, with a bound on
every wait (copied from `chip_smoke.py`'s pattern: the benchmark imports
nothing of it).  The process that uses this never imports jax."""

from __future__ import annotations

import os
import tempfile
import threading
import time

import ray_tpu


class BenchFailure(Exception):
    """The run cannot give a result; the process exits non-zero."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


def bounded(what: str, seconds: float, fn, *args, **kwargs):
    """Run `fn` with a bound on the wait; name the wait when it expires."""
    box = {}

    def run():
        try:
            box["value"] = fn(*args, **kwargs)
        except BaseException as e:  # re-raised in the caller
            box["error"] = e

    t = threading.Thread(target=run, daemon=True, name=f"wait:{what}")
    t.start()
    t.join(seconds)
    if t.is_alive():
        raise BenchFailure(f"timed out after {seconds:.0f}s waiting for "
                           f"{what}")
    if "error" in box:
        raise box["error"]
    return box["value"]


def get(ref, what: str, seconds: float):
    try:
        return ray_tpu.get(ref, timeout=seconds)
    except ray_tpu.GetTimeoutError:
        raise BenchFailure(
            f"timed out after {seconds:.0f}s waiting for {what}") from None


def wait_chips_free(n: int, what: str, seconds: float = 60.0) -> None:
    """The agent hands a TPU lease's share back only when the process
    that held the chips has exited: this is the wait for that."""
    deadline = time.monotonic() + seconds
    while ray_tpu.available_resources().get("TPU", 0) < n:
        if time.monotonic() > deadline:
            raise BenchFailure(f"timed out after {seconds:.0f}s waiting "
                               f"for {what} to exit and give its chip back")
        time.sleep(0.1)


def pid_gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    return False


def wait_gone(pids, seconds: float = 30.0) -> bool:
    """The chips come back when the agent has reaped the process that
    held them, so this returns at once — unless the cluster's books were
    upset: after the machine stood still for 13.5 s in a run of PR 24 the
    share was back while the replica was still being ended."""
    deadline = time.monotonic() + seconds
    while not all(pid_gone(p) for p in pids):
        if time.monotonic() > deadline:
            return False
        time.sleep(0.1)
    return True


def start(chips: int, rehearse: bool) -> None:
    """`ray_tpu.init` with this run's session files under its TMPDIR.
    A rehearsal pretends the TPU resource; a real run takes what the node
    agent counted and fails when that is not what the cell needs."""
    os.environ.setdefault(
        "RT_TMPDIR", os.path.join(tempfile.gettempdir(), "ray_tpu"))
    # every program of a run goes to the persistent compile cache, however
    # quickly it compiled: set-up after a cell's first run compiles nothing
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    ray_tpu.init(
        resources={"TPU": chips} if rehearse else None,
        # a replica's constructor initialises ~11 GiB of weights and
        # compiles its programs under the deploy health gate
        _system_config={"serve_replica_health_timeout_s": 900.0})
    have = ray_tpu.cluster_resources().get("TPU", 0)
    check(have >= chips, f"the node agent counted {have:g} TPU chip(s); "
                         f"this cell needs {chips}")


def stop() -> None:
    from ray_tpu import serve

    try:
        serve.shutdown()
    except Exception:  # nothing was deployed
        pass
    ray_tpu.shutdown()
