"""One process for each chip: chip counting, the environment a lease gives
its worker, what happens to a worker whose lease held chips, and the
resources a Train worker asks for.  No TPU needed: chips are faked by
`resources={"TPU": n}`, as everywhere in tier-1."""

import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu._private import accelerators
from ray_tpu.train import ScalingConfig


@pytest.mark.parametrize("nodes, chips", [
    (["vfio/vfio", "vfio/0"], 1),                      # control node + 1 chip
    (["vfio/vfio", "vfio/0", "vfio/1", "vfio/2", "vfio/3"], 4),
    (["accel0", "accel1", "accel2", "accel3", "vfio/vfio"], 4),
    (["vfio/vfio"], 0),
    ([], 0),
])
def test_num_tpu_chips_counts_numbered_nodes_only(tmp_path, monkeypatch,
                                                  nodes, chips):
    for rel in nodes:
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.touch()
    monkeypatch.setattr(accelerators, "_DEV_ROOT", str(tmp_path))
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    assert accelerators.num_tpu_chips() == chips


@pytest.mark.parametrize("lease, host, want", [
    # no chip in the lease on a chip host: the CPU backend, or jax would
    # open every chip
    ([], 4, {"JAX_PLATFORMS": "cpu"}),
    ([], 0, {}),
    # fewer chips than the host has: per-process bounds too, or a second
    # such process aborts on libtpu's lockfile
    ([2], 4, {"TPU_VISIBLE_CHIPS": "2",
              "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
              "TPU_PROCESS_BOUNDS": "1,1,1"}),
    ([0, 1], 4, {"TPU_VISIBLE_CHIPS": "0,1",
                 "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,2,1",
                 "TPU_PROCESS_BOUNDS": "1,1,1"}),
    # the whole host: the library's own default topology
    ([0, 1, 2, 3], 4, {"TPU_VISIBLE_CHIPS": "0,1,2,3"}),
    ([0], 1, {"TPU_VISIBLE_CHIPS": "0"}),
])
def test_chip_env(lease, host, want):
    assert accelerators.chip_env(lease, host) == want


def _wait(cond, what, seconds=30.0):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.1)


def test_tpu_lease_worker_is_fresh_and_never_reused():
    """A TPU lease gets a worker no task ran in; when the lease comes
    back the worker exits, and only then do its chips return."""
    ray_tpu.init(num_cpus=2, resources={"TPU": 1},
                 object_store_memory=64 * 1024 * 1024)
    try:
        @ray_tpu.remote
        def cpu_pid():
            return os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS")

        @ray_tpu.remote(num_tpus=1)
        class Chip:
            def pid(self):
                return os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS")

        cpu, none = ray_tpu.get(cpu_pid.remote(), timeout=60)
        assert none is None
        first = Chip.remote()
        pid1, chips1 = ray_tpu.get(first.pid.remote(), timeout=60)
        assert chips1 == "0" and pid1 != cpu  # not the used CPU worker
        ray_tpu.kill(first)
        # the one chip is leased again only once the first holder is gone
        second = Chip.remote()
        pid2, chips2 = ray_tpu.get(second.pid.remote(), timeout=60)
        assert chips2 == "0" and pid2 not in (pid1, cpu)
        with pytest.raises(ProcessLookupError):
            os.kill(pid1, 0)
    finally:
        ray_tpu.shutdown()


def test_returned_tpu_lease_worker_is_not_idled():
    """A TPU *task*'s lease returns after its linger: the worker must not
    go back on the idle list (its jax would still hold the chip)."""
    ray_tpu.init(num_cpus=2, resources={"TPU": 1},
                 object_store_memory=64 * 1024 * 1024)
    try:
        @ray_tpu.remote(num_tpus=1)
        def chip_pid():
            return os.getpid()

        pid = ray_tpu.get(chip_pid.remote(), timeout=60)

        def gone():
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                return True
            return False

        _wait(gone, "the TPU task's worker to exit after its lease returned",
              seconds=60)
        _wait(lambda: ray_tpu.available_resources().get("TPU", 0) == 1,
              "the chip to come back")
    finally:
        ray_tpu.shutdown()


def _gone(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    return False


def test_removed_bundle_keeps_its_tpu_until_the_holder_is_gone(tmp_path):
    """Removing a placement group hands the bundle back at once, but not
    the TPU share of a lease whose worker still holds the chip: a TPU
    lease granted in that window would find no chip index free."""
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)

    ray_tpu.init(num_cpus=2, resources={"TPU": 1},
                 object_store_memory=64 * 1024 * 1024)
    try:
        pg = placement_group([{"TPU": 1, "CPU": 1}]).ready(timeout=30)
        pid_file = tmp_path / "pid"

        @ray_tpu.remote(num_tpus=1, max_retries=0)
        def hold(path):
            with open(path, "w") as f:
                f.write(str(os.getpid()))
            time.sleep(120)

        @ray_tpu.remote(num_tpus=1)
        def chip():
            return os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS")

        hold.options(placement_group=pg).remote(str(pid_file))
        _wait(lambda: pid_file.exists() and pid_file.read_text(),
              "the bundle's TPU task to start")
        pid1 = int(pid_file.read_text())
        # a stopped process takes no SIGTERM: it lives until the agent's
        # SIGKILL, 5 s after the bundle's removal
        os.kill(pid1, signal.SIGSTOP)
        remove_placement_group(pg)
        _wait(lambda: ray_tpu.available_resources().get("CPU", 0) == 2,
              "the bundle's CPU to come back")
        ready = chip.remote()  # asks the node pool for the one chip
        for _ in range(20):
            assert _gone(pid1) or \
                ray_tpu.available_resources().get("TPU", 0) == 0, \
                "the TPU resource came back while its holder lives"
            time.sleep(0.1)
        pid2, chips2 = ray_tpu.get(ready, timeout=60)
        assert chips2 == "0" and pid2 != pid1 and _gone(pid1)
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("tpu", [0.5, 1.5])
def test_a_share_of_a_chip_fails_the_lease(tpu):
    """A chip belongs to one process: a fractional TPU demand is refused
    with its reason, not granted a worker on the CPU backend."""
    ray_tpu.init(num_cpus=2, resources={"TPU": 2},
                 object_store_memory=64 * 1024 * 1024)
    try:
        @ray_tpu.remote(resources={"TPU": tpu}, max_retries=0)
        def backend():
            return os.environ.get("JAX_PLATFORMS"), \
                os.environ.get("TPU_VISIBLE_CHIPS")

        with pytest.raises(ray_tpu.SchedulingError,
                           match="chips are leased whole"):
            ray_tpu.get(backend.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()


def test_use_tpu_asks_for_the_chips_one_host_has():
    ray_tpu.init(num_cpus=2, resources={"TPU": 1},
                 object_store_memory=64 * 1024 * 1024)
    try:
        assert ScalingConfig(use_tpu=True).worker_resources() == {"TPU": 1}
        assert ScalingConfig(use_tpu=True, resources_per_worker={"TPU": 2}
                             ).worker_resources() == {"TPU": 2}

        @ray_tpu.remote(resources=ScalingConfig(use_tpu=True)
                        .worker_resources())
        class W:
            def ok(self):
                return True

        assert ray_tpu.get(W.remote().ok.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()
