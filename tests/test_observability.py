"""Metrics + state API + task events + timeline tests.

Mirrors the reference's observability suites
(reference: python/ray/tests/test_metrics_agent.py,
test_state_api.py; stats plane src/ray/stats/metric.h, task events
src/ray/core_worker/task_event_buffer.h:206)."""

import time
import urllib.request

import pytest

import ray_tpu


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    try:
        yield ray_tpu
    finally:
        ray_tpu.shutdown()


def _scrape(port: int) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=10) as r:
        return r.read().decode()


def _assert_valid_exposition(text: str) -> None:
    """Validate Prometheus text exposition format (the contract every
    scraper relies on): HELP/TYPE headers come at most once per family,
    a family's samples are contiguous, sample lines parse as
    name{labels} value, and histogram buckets are cumulative with a
    +Inf terminal matching _count."""
    import re

    sample_re = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'               # metric name
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'         # first label
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'    # more labels
        r' [-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|nan|inf)$')
    typed: dict = {}
    helped: set = set()
    family_of_sample = {}
    last_family = None
    families_seen_done = set()
    for i, ln in enumerate(text.splitlines()):
        if not ln.strip():
            continue
        if ln.startswith("# HELP "):
            name = ln.split()[2]
            assert name not in helped, f"duplicate HELP for {name}"
            helped.add(name)
            continue
        if ln.startswith("# TYPE "):
            parts = ln.split()
            name, kind = parts[2], parts[3]
            assert name not in typed, f"duplicate TYPE for {name}"
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), ln
            typed[name] = kind
            continue
        assert not ln.startswith("#"), f"bad comment line: {ln!r}"
        m = sample_re.match(ln)
        assert m, f"unparsable sample line {i}: {ln!r}"
        name = m.group(1)
        base = name
        for suffix in ("_bucket", "_count", "_sum"):
            if name.endswith(suffix) and name[:-len(suffix)] in typed:
                base = name[:-len(suffix)]
        family_of_sample[name] = base
        # contiguity: once a family ends, it must not reappear
        if base != last_family:
            assert base not in families_seen_done, \
                f"family {base} interleaved (line {i}: {ln!r})"
            if last_family is not None:
                families_seen_done.add(last_family)
            last_family = base
    # histogram buckets cumulative and consistent with _count
    for fam, kind in typed.items():
        if kind != "histogram":
            continue
        buckets: dict = {}
        counts: dict = {}
        for ln in text.splitlines():
            if ln.startswith(fam + "_bucket"):
                labels = ln[len(fam + "_bucket"):].split(" ")[0]
                le = labels.split('le="')[1].split('"')[0]
                key = labels.replace(f'le="{le}"', "").strip("{},")
                buckets.setdefault(key, []).append(float(ln.rsplit(" ", 1)[1]))
            elif ln.startswith(fam + "_count"):
                labels, v = ln[len(fam + "_count"):].rsplit(" ", 1)
                counts[labels.strip("{}")] = float(v)
        for key, vals in buckets.items():
            assert vals == sorted(vals), \
                f"{fam} buckets not cumulative for {{{key}}}: {vals}"
            if key in counts:
                assert vals[-1] == counts[key], \
                    f"{fam} +Inf bucket != _count for {{{key}}}"


def _agent_metrics_port() -> int:
    w = ray_tpu.api._worker()
    return w.agent.call("metrics_port")["port"]


def _head_metrics_port() -> int:
    w = ray_tpu.api._worker()
    return w.head.call("metrics_port")["port"]


def test_agent_prometheus_endpoint(cluster):
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(1), timeout=60) == 2
    port = _agent_metrics_port()
    assert port > 0
    text = _scrape(port)
    assert "rt_object_store_capacity_bytes" in text
    assert "rt_worker_pool_size" in text
    # the worker that executed f pushes its counters for re-export
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        text = _scrape(port)
        if "rt_tasks_finished" in text:
            return
        time.sleep(0.5)
    raise AssertionError("worker metrics never re-exported:\n" + text[:800])


def test_head_prometheus_endpoint(cluster):
    port = _head_metrics_port()
    assert port > 0
    text = _scrape(port)
    assert "rt_head_nodes" in text
    assert "rt_head_nodes 1.0" in text or "rt_head_nodes 1 " in text \
        or "rt_head_nodes 1\n" in text


def test_metrics_exposition_format_valid(cluster):
    """Both scrape targets must emit parseable Prometheus exposition
    text — guards the handcrafted renderer (and the merge of worker
    pushes) against format drift as metrics are added."""
    @ray_tpu.remote
    def f(x):
        return x

    ray_tpu.get([f.remote(i) for i in range(20)], timeout=60)
    head_port, agent_port = _head_metrics_port(), _agent_metrics_port()
    deadline = time.monotonic() + 60
    head_text = agent_text = ""
    while time.monotonic() < deadline:
        head_text, agent_text = _scrape(head_port), _scrape(agent_port)
        # wait until the interesting families are present so the
        # validation actually covers them (worker push + head ingest +
        # the introspection loop-lag probes on both daemons)
        if "ray_tpu_task_sched_latency_seconds_bucket" in head_text \
                and "rt_tasks_finished" in agent_text \
                and "ray_tpu_event_loop_lag_seconds" in head_text \
                and "ray_tpu_event_loop_lag_seconds" in agent_text:
            break
        time.sleep(0.5)
    _assert_valid_exposition(head_text)
    _assert_valid_exposition(agent_text)
    # the new head-side families are exposed
    assert "ray_tpu_task_sched_latency_seconds" in head_text
    for phase in ("queued", "leased", "running"):
        assert f'phase="{phase}"' in head_text, phase
    assert "rt_head_traces" in head_text
    # always-on introspection gauges: the loop-lag probe on each daemon
    # and the owner-side dispatch-pump depth riding the worker push
    assert 'ray_tpu_event_loop_lag_seconds{role="head"}' in head_text
    assert 'role="agent"' in agent_text
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        if "ray_tpu_dispatch_pump_depth" in agent_text:
            break
        time.sleep(0.5)
        agent_text = _scrape(agent_port)
    assert "ray_tpu_dispatch_pump_depth" in agent_text
    _assert_valid_exposition(agent_text)
    # tracing self-metrics ride the worker push to the agent endpoint
    deadline = time.monotonic() + 45
    while time.monotonic() < deadline:
        agent_text = _scrape(agent_port)
        if "rt_trace_spans_sampled" in agent_text:
            break
        time.sleep(0.5)
    assert "rt_trace_spans_sampled" in agent_text
    _assert_valid_exposition(agent_text)


def test_user_metrics_exported(cluster):
    from ray_tpu.util.metrics import Counter

    @ray_tpu.remote
    def instrumented():
        c = Counter("my_app_events", "app-level counter")
        c.inc(3)
        return "ok"

    assert ray_tpu.get(instrumented.remote(), timeout=60) == "ok"
    port = _agent_metrics_port()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if "my_app_events" in _scrape(port):
            return
        time.sleep(0.5)
    raise AssertionError("user metric never appeared on the node endpoint")


def test_list_tasks_and_summary(cluster):
    from ray_tpu.util.state import list_tasks, summarize_tasks

    @ray_tpu.remote
    def traced(x):
        return x

    ray_tpu.get([traced.remote(i) for i in range(5)], timeout=60)
    # NB: tasks defined inside a test function carry their qualname
    # ("test_x.<locals>.traced") — filter by suffix
    deadline = time.monotonic() + 15
    finished = []
    while time.monotonic() < deadline:
        finished = [t for t in list_tasks()
                    if t.get("name", "").endswith("traced")
                    and t.get("state") == "FINISHED"]
        if len(finished) >= 5:
            break
        time.sleep(0.3)
    assert len(finished) >= 5, finished
    t = finished[0]
    assert t["worker_id"] and t["node_id"]
    assert t.get("running_ts") and t.get("finished_ts")
    summary = summarize_tasks()
    traced_rows = [v for k, v in summary.items() if k.endswith("traced")]
    assert traced_rows and traced_rows[0]["states"].get("FINISHED", 0) >= 5
    # grown to percentiles: the running-phase stats cover the 5 runs
    running = traced_rows[0]["running"]
    assert running and running["count"] >= 5
    assert running["p50_ms"] <= running["p99_ms"] <= running["max_ms"]


def test_failed_task_recorded(cluster):
    from ray_tpu.util.state import list_tasks

    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("kaput")

    with pytest.raises(ray_tpu.RayError):
        ray_tpu.get(boom.remote(), timeout=60)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        failed = [t for t in list_tasks(state="FAILED")
                  if t.get("name", "").endswith("boom")]
        if failed:
            assert "kaput" in failed[0].get("error", "")
            return
        time.sleep(0.3)
    raise AssertionError("failed task never recorded")


def test_timeline_chrome_trace(cluster, tmp_path):
    import json

    from ray_tpu.util.state import timeline

    @ray_tpu.remote
    def span():
        time.sleep(0.05)
        return 1

    ray_tpu.get([span.remote() for _ in range(3)], timeout=60)
    path = str(tmp_path / "trace.json")
    deadline = time.monotonic() + 15
    events = []
    while time.monotonic() < deadline:
        events = [e for e in timeline(path)
                  if e["name"].endswith("span")]
        if len(events) >= 3:
            break
        time.sleep(0.3)
    assert len(events) >= 3
    ev = events[0]
    assert ev["ph"] == "X" and ev["dur"] >= 50_000  # >=50ms in usecs
    assert json.load(open(path))  # valid JSON on disk


def test_list_objects(cluster):
    import numpy as np

    from ray_tpu.util.state import list_objects

    ref = ray_tpu.put(np.zeros(300_000))  # ~2.4MB -> plasma
    objs = list_objects()
    assert any(o["object_id"] == ref.oid for o in objs), objs
    assert all("size" in o and "node_id" in o for o in objs)
    del ref


def test_metric_names_documented_in_readme(cluster):
    """Every framework metric family registered at runtime must appear
    in README.md's Observability metrics table — undocumented metrics
    fail CI (VERDICT/ISSUE 6 satellite).  Covers both what the live
    endpoints expose and every process-singleton family the codebase
    can register lazily (dag/serve/xfer/introspection helpers)."""
    import os

    @ray_tpu.remote
    def f(x):
        return x

    ray_tpu.get([f.remote(i) for i in range(10)], timeout=60)
    head_port, agent_port = _head_metrics_port(), _agent_metrics_port()
    deadline = time.monotonic() + 30
    names = set()
    while time.monotonic() < deadline:
        text = _scrape(head_port) + _scrape(agent_port)
        names = {ln.split()[2] for ln in text.splitlines()
                 if ln.startswith("# TYPE ")}
        if "rt_tasks_finished" in names:
            break
        time.sleep(0.5)
    # force-register every lazy singleton family so the diff also
    # covers code paths this test didn't exercise (dag, serve, xfer)
    from ray_tpu._private import metrics as m

    for fn in (m.object_transfer_metrics, m.dag_metrics,
               m.serve_request_latency_histogram, m.loop_lag_gauge,
               m.dispatch_pump_depth_gauge, m.dag_channel_occupancy_gauge,
               m.serve_proxy_inflight_gauge, m.fault_tolerance_metrics,
               m.task_events_dropped_counter,
               m.dispatch_batch_size_histogram,
               m.object_leaked_bytes_gauge,
               m.memory_scan_partial_gauge,
               m.object_store_breakdown_gauge,
               m.pipeline_metrics,
               m.llm_metrics,
               m.llm_prefix_metrics,
               m.llm_block_metrics,
               m.autoscaler_metrics,
               m.serve_sheds_counter,
               m.deadline_metrics,
               m.serve_tail_metrics,
               m.memory_pressure_metrics,
               m.object_checksum_failures_counter,
               m.head_inbox_depth_gauge):
        fn()
    with m.default_registry._lock:
        names |= set(m.default_registry._metrics)
    framework = sorted(n for n in names
                       if n.startswith(("rt_", "ray_tpu_")))
    assert framework, "no framework metrics scraped at all?"
    readme = open(os.path.join(os.path.dirname(__file__), "..",
                               "README.md")).read()
    undocumented = [n for n in framework if n not in readme]
    assert not undocumented, (
        f"metrics registered at runtime but missing from the README "
        f"metrics table: {undocumented}")


def test_head_dashboard_spa(local_cluster):
    """The head serves the single-page dashboard app and its JSON data
    plane, and the snapshot reflects live cluster state (reference:
    dashboard/client/src — the role, not the framework)."""
    import json
    import urllib.request

    import ray_tpu as rt

    port = rt.api._worker().head.call("metrics_port")["port"]
    assert port

    def fetch(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.headers.get("Content-Type", ""), r.read()

    # app shell + the one JS file
    ct, html = fetch("/")
    assert ct.startswith("text/html")
    assert "ray_tpu cluster" in html.decode()
    assert '<script src="/app.js">' in html.decode()
    ct, js = fetch("/app.js")
    assert ct.startswith("application/javascript")
    for needle in ("api/snapshot", "sparkline", "Placement groups",
                   "Traces", "Memory", "api/memory"):
        assert needle in js.decode()

    # live state lands in the snapshot the app renders from
    @rt.remote
    def probe():
        return 1

    assert rt.get(probe.remote(), timeout=60) == 1

    @rt.remote
    class DashActor:
        def ping(self):
            return "pong"

    a = DashActor.remote()
    assert rt.get(a.ping.remote(), timeout=60) == "pong"

    snap = json.loads(fetch("/api/snapshot")[1])
    for key in ("nodes", "actors", "tasks", "placement_groups", "jobs",
                "traces", "series", "summary"):
        assert key in snap, key
    assert len(snap["nodes"]) == 1
    assert any(x["state"] == "ALIVE" for x in snap["actors"])
    assert any(t.get("state") == "FINISHED" for t in snap["tasks"])
    assert snap["summary"]["cpus_total"] > 0

    # timeline download is a Chrome trace event list: duration slices
    # plus flow events ("s"/"f" submit→execute arrows) and optional
    # instant events for queue-time failures.  Poll: the executor's
    # RUNNING/FINISHED events flush within ms but the owner's SUBMITTED
    # half (which the flow start needs) rides the periodic flush tick.
    deadline = time.monotonic() + 45
    while True:
        events = json.loads(fetch("/api/timeline")[1])
        assert isinstance(events, list) and events
        assert all(e["ph"] in ("X", "s", "f", "i") and "ts" in e
                   for e in events)
        slices = [e for e in events if e["ph"] == "X"]
        assert slices and all("dur" in e for e in slices)
        flow_starts = {e["id"] for e in events if e["ph"] == "s"}
        flow_ends = {e["id"] for e in events if e["ph"] == "f"}
        if flow_starts or time.monotonic() >= deadline:
            break
        time.sleep(0.5)
    assert flow_starts and flow_starts == flow_ends

    # legacy summary endpoint unchanged
    state = json.loads(fetch("/api/state")[1])
    assert len(state["nodes"]) == 1 and "actors_by_state" in state
    rt.kill(a)
