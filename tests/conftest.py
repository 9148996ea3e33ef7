"""Test fixtures.

Multi-device tests run on a virtual 8-device CPU mesh
(reference test strategy: SURVEY.md §4.3 — JAX CPU
``xla_force_host_platform_device_count`` emulates multi-device meshes
without hardware).  JAX_PLATFORMS=cpu is forced here and inherited by
every daemon and worker the tests spawn; the chip path is proved by
`chip_smoke.py` on a machine that has one, never by these tests.

Every test runs under a time limit (below): a hang costs that one test,
with the stacks of all threads printed, not the driver's whole run.
"""

import faulthandler
import os
import signal
import sys

# Env for spawned daemons/workers (inherited): pure-CPU jax with a
# virtual 8-device mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


# ------------------------------------------------------- per-test time limit
# Ten times the slowest tier-1 test measured here (28 s), so a slower
# machine does not trip it, and a fifth of the driver's limit for the run.
_TEST_TIMEOUT_S = 300
# after the limit has fired once, what the rest of the test (its
# teardown) may still take before it fires again
_TEST_TIMEOUT_AGAIN_S = 60
# last resort when the main thread cannot take a signal (stuck inside a
# C call): dump and kill this process — under xdist that fails the test
# and the worker is replaced
_TEST_TIMEOUT_HARD_S = _TEST_TIMEOUT_S + 2 * _TEST_TIMEOUT_AGAIN_S + 30


def _on_test_timeout(signum, frame):
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    pytest.fail(f"test exceeded its {_TEST_TIMEOUT_S}s time limit "
                f"(stacks of all threads are on stderr)", pytrace=True)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """One limit around the whole of setup/call/teardown."""
    old = signal.signal(signal.SIGALRM, _on_test_timeout)
    signal.setitimer(signal.ITIMER_REAL, _TEST_TIMEOUT_S,
                     _TEST_TIMEOUT_AGAIN_S)
    faulthandler.dump_traceback_later(_TEST_TIMEOUT_HARD_S, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# --------------------------------------------------------- leak tripwire
# Per-module snapshots of this process's thread, socket, and RSS
# footprint.  A cluster that truly tears down returns all three to
# baseline; a leak (an EventLoopThread or RpcClient surviving shutdown,
# a cache pinning arena views) compounds module over module.  The
# signature is a rising LOW-WATER mark: a module snapshotted
# mid-teardown spikes high but the next quiet module drops back, while
# a genuine leak lifts the floor of every later snapshot — so compare
# window minima, not per-module deltas.  Thread/socket trips FAIL;
# the RSS trip is informational under tier-1 (-m 'not slow') and fails
# full runs, like the wall-clock tripwire — the allocator's reluctance
# to return pages makes RSS the noisiest of the three.

_RESOURCE_HISTORY = []  # (module_name, threads, sockets, rss_mb)
_LEAK_WINDOW = 5        # modules per comparison window
_LEAK_FLOOR = 25        # min rise between window floors that trips
_RSS_FLOOR_MB = 300     # min RSS-floor rise (MiB) that trips


def _read_rss_mb():
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return (pages * os.sysconf("SC_PAGE_SIZE")) // (1024 * 1024)
    except (OSError, ValueError, IndexError):
        return 0


def _count_threads_sockets():
    import gc
    import threading

    # A shut-down cluster's event-loop socketpairs close on GC, not on
    # shutdown(): dead drivers pile up until a gen-2 collection, whose
    # period can exceed the comparison window — without this collect the
    # floor rises on GC lag alone.  Truly pinned components (a global
    # root holding a worker/loop) survive the collect and still trip.
    gc.collect()
    threads = threading.active_count()
    sockets = 0
    try:
        fd_dir = "/proc/self/fd"
        for fd in os.listdir(fd_dir):
            try:
                if os.readlink(os.path.join(fd_dir, fd)).startswith(
                        "socket:"):
                    sockets += 1
            except OSError:
                pass
    except OSError:
        pass
    return threads, sockets


def _monotonic_leak(history, window=_LEAK_WINDOW, floor=_LEAK_FLOOR,
                    rss_floor=_RSS_FLOOR_MB):
    """(kind, tail) when a resource's low-water mark over the last
    `window` modules sits >= its floor above its low-water mark over
    the preceding `window` modules, else None.  Minima filter transient
    spikes (a module snapshotted while its cluster is still closing);
    a real leak raises every later module's floor.  History tuples may
    omit the trailing rss_mb field (older snapshots).  Pure so the
    detector itself is unit-testable."""
    if len(history) < 2 * window:
        return None
    prev = history[-2 * window:-window]
    tail = history[-window:]
    for idx, kind, fl in ((1, "threads", floor), (2, "sockets", floor),
                          (3, "rss_mb", rss_floor)):
        if any(len(h) <= idx for h in prev + tail):
            continue
        if (min(h[idx] for h in tail)
                - min(h[idx] for h in prev)) >= fl:
            return kind, tail
    return None


@pytest.fixture(scope="module", autouse=True)
def resource_leak_tripwire(request):
    """Snapshot thread/socket/RSS after every test module and flag
    monotonic growth across cluster setup/teardown cycles.  Thread and
    socket trips fail outright; the RSS trip warns under tier-1
    (-m 'not slow') and fails full runs."""
    yield
    threads, sockets = _count_threads_sockets()
    _RESOURCE_HISTORY.append(
        (request.module.__name__, threads, sockets, _read_rss_mb()))
    hit = _monotonic_leak(_RESOURCE_HISTORY)
    if hit is None:
        return
    kind, tail = hit
    detail = ", ".join(f"{name}={t}/{s}/{r}MB" for name, t, s, r in tail)
    msg = (f"resource leak tripwire: the {kind} low-water mark rose "
           f">= {_RSS_FLOOR_MB if kind == 'rss_mb' else _LEAK_FLOOR} "
           f"across the last {_LEAK_WINDOW} test modules "
           f"(module=threads/sockets/rss: {detail}) — a cluster "
           f"component is surviving shutdown()")
    if kind == "rss_mb" and _is_tier1(request.config):
        import warnings

        warnings.warn(msg)
        return
    pytest.fail(msg)


# -------------------------------------------- module wall-clock tripwire
# The driver runs tier-1 under 6 xdist workers (--dist loadfile) against
# a 1470s wall; a whole run takes about 150s here, and with loadfile the
# slowest module bounds it.  Every run prints a per-module duration
# table in the pytest terminal summary; any single FAST module (its
# non-slow tests only) above _MODULE_BUDGET_S is flagged —
# informationally under tier-1 (-m 'not slow'), as a session FAILURE
# otherwise, so full runs catch the regression first.

_MODULE_BUDGET_S = 45.0
_MODULE_DURATIONS = {}  # module path -> accumulated fast-test seconds
_SLOW_NODES = set()     # nodeids carrying @pytest.mark.slow


def _module_budget_violations(durations, budget=_MODULE_BUDGET_S):
    """[(module, seconds)] over budget, worst first.  Pure so the
    tripwire itself is unit-testable."""
    return sorted(((m, d) for m, d in durations.items() if d > budget),
                  key=lambda kv: -kv[1])


def _is_tier1(config) -> bool:
    # the tier-1 invocation deselects slow tests via -m 'not slow'
    return "not slow" in (getattr(config.option, "markexpr", "") or "")


def pytest_collection_modifyitems(config, items):
    for it in items:
        if it.get_closest_marker("slow"):
            _SLOW_NODES.add(it.nodeid)


def pytest_runtest_logreport(report):
    if report.when not in ("setup", "call", "teardown"):
        return
    if report.nodeid in _SLOW_NODES:
        return  # slow tests have their own (non-tier-1) time budget
    mod = report.nodeid.split("::", 1)[0]
    _MODULE_DURATIONS[mod] = (_MODULE_DURATIONS.get(mod, 0.0)
                              + (report.duration or 0.0))


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _MODULE_DURATIONS:
        return
    tr = terminalreporter
    ranked = sorted(_MODULE_DURATIONS.items(), key=lambda kv: -kv[1])
    tr.section(f"per-module wall clock (fast tests; budget "
               f"{_MODULE_BUDGET_S:.0f}s/module)")
    for mod, d in ranked[:15]:
        flag = "  << OVER BUDGET" if d > _MODULE_BUDGET_S else ""
        tr.write_line(f"{d:8.1f}s  {mod}{flag}")
    total = sum(_MODULE_DURATIONS.values())
    tr.write_line(f"{total:8.1f}s  TOTAL in this process (the driver's "
                  f"wall for tier-1: 1470s under 6 workers)")
    over = _module_budget_violations(_MODULE_DURATIONS)
    if over:
        names = ", ".join(f"{m} ({d:.0f}s)" for m, d in over)
        if _is_tier1(config):
            tr.write_line(
                f"WARNING: fast module(s) over the {_MODULE_BUDGET_S:.0f}s "
                f"budget: {names} — move tests behind @pytest.mark.slow "
                f"or speed them up: under --dist loadfile one module "
                f"runs on one worker")
        else:
            tr.write_line(
                f"ERROR: fast module(s) over the {_MODULE_BUDGET_S:.0f}s "
                f"budget: {names} (failing the session; informational "
                f"under -m 'not slow')")


def pytest_sessionfinish(session, exitstatus):
    # the tripwire FAILS full (non-tier-1) runs so budget regressions
    # surface locally; under tier-1 it stays informational — the tier-1
    # driver run must never be failed retroactively by a watchdog
    if exitstatus != 0 or _is_tier1(session.config):
        return
    if _module_budget_violations(_MODULE_DURATIONS):
        session.exitstatus = 1


def force_cpu_jax():
    """In-process override for a test process started without
    JAX_PLATFORMS in its environment: select CPU before first use."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    return jax


@pytest.fixture(scope="session")
def cpu_jax():
    return force_cpu_jax()


@pytest.fixture
def local_cluster():
    """A started single-node ray_tpu cluster; shuts down after the test."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)
    try:
        yield ray_tpu
    finally:
        ray_tpu.shutdown()
