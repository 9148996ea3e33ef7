#!/usr/bin/env python
"""Benchmark harness. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extras": {...}}

Headline: single-client sync task throughput, directly comparable to the
reference's ray_perf.py microbenchmark ("single client tasks sync",
reference: python/ray/_private/ray_perf.py:174; recorded value 1006.9
tasks/s in release/release_logs/2.9.3/microbenchmark.json).

Also measured (extras): async task throughput, actor call throughput,
object-store put bandwidth, and the other host phases.  What the chip
does is `benchmarks/run.py`'s to measure (BENCHMARK.json).

Robustness contract (the driver runs this unattended):
  * every host phase is individually try/except'ed with its own timeout —
    one hang or crash cannot erase numbers already measured;
  * the JSON line is ALWAYS printed, with per-phase errors in
    extras["errors"]; a run with errors exits 1.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def bench_tasks_sync(ray_tpu, n=300):
    @ray_tpu.remote
    def e():
        return b"ok"

    ray_tpu.get(e.remote(), timeout=60)  # warm lease
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(e.remote(), timeout=60)
    return n / (time.perf_counter() - t0)

def bench_tasks_async(ray_tpu, n=2000):
    @ray_tpu.remote
    def e():
        return b"ok"

    ray_tpu.get([e.remote() for _ in range(50)], timeout=60)
    t0 = time.perf_counter()
    ray_tpu.get([e.remote() for _ in range(n)], timeout=120)
    return n / (time.perf_counter() - t0)

def bench_actor(ray_tpu, n_sync=300, n_async=2000):
    @ray_tpu.remote
    class A:
        def m(self):
            return b"ok"

    a = A.remote()
    ray_tpu.get(a.m.remote(), timeout=60)
    t0 = time.perf_counter()
    for _ in range(n_sync):
        ray_tpu.get(a.m.remote(), timeout=60)
    sync = n_sync / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    ray_tpu.get([a.m.remote() for _ in range(n_async)], timeout=120)
    return sync, n_async / (time.perf_counter() - t0)

def bench_burst_then_async(ray_tpu, burst=2000, n=2000):
    """Burst-independence phase (round-5 verdict top finding): 2000
    BLOCKING sync round trips used to train the owner's per-function
    service-time estimator into serializing dispatch, collapsing the
    async rate that follows from ~5k/s to ~1.5k/s.  With depth driven by
    worker-reported execution time this rate must track
    tasks_async_per_s (the fresh-process async run) within noise."""
    @ray_tpu.remote
    def e():
        return b"ok"

    ray_tpu.get(e.remote(), timeout=60)
    for _ in range(burst):
        ray_tpu.get(e.remote(), timeout=60)
    t0 = time.perf_counter()
    ray_tpu.get([e.remote() for _ in range(n)], timeout=120)
    return n / (time.perf_counter() - t0)

def _client_bench(address: str, n: int, ready_file: str = ""):
    """One concurrent driver (runs as a subprocess): connect to the
    shared cluster, fire n async tasks, print one parseable line.
    With a ready_file, clients barrier on it after warming so every
    burst window overlaps — the union-window aggregate then measures
    contention, not per-client interpreter startup skew."""
    import ray_tpu

    ray_tpu.init(address=address)

    @ray_tpu.remote
    def e():
        return b"ok"

    ray_tpu.get([e.remote() for _ in range(50)], timeout=60)
    if ready_file:
        print("CLIENTREADY", flush=True)
        deadline = time.time() + 60
        while not os.path.exists(ready_file) and time.time() < deadline:
            time.sleep(0.01)
    t0 = time.time()  # absolute: the parent unions windows across clients
    ray_tpu.get([e.remote() for _ in range(n)], timeout=120)
    t1 = time.time()
    print("CLIENTJSON " + json.dumps(
        {"tasks": n, "wall_s": round(t1 - t0, 4),
         "start": round(t0, 4), "end": round(t1, 4)}))
    ray_tpu.shutdown()

def _head_scaling_probe(ray_tpu):
    """Best-effort head-side sample after a client-count round: the
    sched-latency SLO p99 and per-shard ingest loop lag (the sharded
    head's 'which plane is hot' signal)."""
    try:
        snap = ray_tpu.api._worker().head.call("autoscaler_snapshot",
                                               timeout=15)
    except Exception:
        return None, {}
    p99 = (snap.get("signals") or {}).get("sched_queued_p99_ms")
    lags = {name: round(float(p.get("lag_s", 0.0)) * 1000.0, 3)
            for name, p in ((snap.get("shards") or {}).get("planes")
                            or {}).items()}
    return p99, lags

def bench_head_scaling(ray_tpu, n=800, pairs=2, counts=(2, 4, 8, 16),
                       probe=True):
    """Head-scalability phase (ISSUE 8, extended by ISSUE 18): aggregate
    multi-driver task throughput at 2..16 concurrent clients sharing one
    cluster.  Every client's lease requests, task-event flushes, and
    heartbeat-fed directory traffic land on the same head/agent — this
    is the phase that shows whether one control-plane structure is the
    ceiling.  Cycled BEST-OF ALTERNATING rounds per the slow-box
    protocol; scaling_efficiency_pct is per-client throughput retained
    from 2 to 8 clients (100 * rate8 / (4 * rate2)).  Also emits the
    sched_p99_ms_by_clients curve and per-shard ingest loop lag sampled
    right after each client count's best round."""
    rates = {c: [] for c in counts}
    p99_curve = {}
    shard_lag = {}
    for _ in range(pairs):
        for c in counts:
            rates[c].append(bench_multi_client(ray_tpu, clients=c, n=n))
            if probe:
                p99, lags = _head_scaling_probe(ray_tpu)
                if p99 is not None:
                    p99_curve[str(c)] = p99
                if lags:
                    shard_lag = lags
    best = {c: max(v) for c, v in rates.items()}
    eff = 100.0 * best[8] / (4 * best[2]) if best.get(2) else 0.0
    out = {
        "multi_client_2_tasks_per_s": round(best[2], 1),
        "multi_client_tasks_per_s": round(best[8], 1),
        "scaling_efficiency_pct": round(eff, 1),
    }
    if 4 in best:
        out["multi_client_4_tasks_per_s"] = round(best[4], 1)
    if 16 in best:
        out["multi_client_16_tasks_per_s"] = round(best[16], 1)
        out["scaling_efficiency_16_pct"] = round(
            100.0 * best[16] / (8 * best[2]), 1) if best.get(2) else 0.0
    if p99_curve:
        out["sched_p99_ms_by_clients"] = p99_curve
    if shard_lag:
        out["head_shard_loop_lag_ms"] = shard_lag
    return out

def _head_scaling_ab_bench(shards: int):
    """Runs as a subprocess: its OWN cluster with RT_HEAD_INGEST_SHARDS
    pinned, a reduced 2/8-client ladder, one JSON line out — the
    single-loop (shards=0) side of the head scale-out A/B.  The main
    phase's numbers come from the default (sharded) head; this run is
    the control."""
    os.environ["RT_HEAD_INGEST_SHARDS"] = str(shards)
    import ray_tpu

    ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4),
                 object_store_memory=256 * 1024 * 1024)
    try:
        out = bench_head_scaling(ray_tpu, pairs=2, counts=(2, 8),
                                 probe=False)
        print("HEADSCALEJSON " + json.dumps({
            "head_ingest_shards": shards,
            "multi_client_2_tasks_per_s":
                out["multi_client_2_tasks_per_s"],
            "multi_client_tasks_per_s": out["multi_client_tasks_per_s"],
            "scaling_efficiency_pct": out["scaling_efficiency_pct"],
        }))
    finally:
        ray_tpu.shutdown()

def bench_head_scaling_single_loop_ab():
    """The A/B control: the same multi-client ladder against a
    single-loop head (head_ingest_shards=0) in a subprocess cluster.
    Keys are suffixed _single_loop; scaling_efficiency_vs_single_loop_x
    is the headline ratio (> 1 = the shards pay for themselves)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--head-scaling-bench", "0"],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    for line in proc.stdout.splitlines():
        if line.startswith("HEADSCALEJSON "):
            r = json.loads(line[len("HEADSCALEJSON "):])
            return {
                "multi_client_tasks_per_s_single_loop":
                    r["multi_client_tasks_per_s"],
                "scaling_efficiency_pct_single_loop":
                    r["scaling_efficiency_pct"],
            }
    raise RuntimeError(
        f"head-scaling A/B rc={proc.returncode}: {proc.stderr[-400:]}")

def bench_multi_client(ray_tpu, clients=3, n=1000):
    """Aggregate throughput with several concurrent DRIVER processes
    sharing one cluster — the owners contend for the same agents'
    leases, which is where history-dependent dispatch and greedy lease
    retention show up as cross-client interference.  The rate is total
    tasks over the UNION of the clients' measured burst windows
    (min start → max end, absolute stamps on one host clock), so
    interpreter/jax startup — seconds per client, pure noise for the
    control-plane question — stays out of the denominator, while
    non-overlapping windows can't overstate the aggregate."""
    addr = "%s:%d" % tuple(ray_tpu.api._worker().head_addr)
    ready_file = os.path.join(
        "/tmp", f"rt-bench-go-{os.getpid()}-{time.monotonic_ns()}")
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py"), "--client-bench",
         addr, str(n), ready_file], stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, cwd=REPO)
        for _ in range(clients)]
    # start barrier: wait for every client to finish init+warm, then
    # release them together so the measured windows overlap.  select()
    # with a deadline: a wedged client must not hang the whole phase
    import select as _select

    deadline = time.time() + 120
    for p in procs:
        ready = False
        while time.time() < deadline:
            r, _w, _x = _select.select([p.stdout], [], [], 1.0)
            if not r:
                continue
            line = p.stdout.readline()
            if not line:
                break  # EOF: client died during init
            if line.startswith("CLIENTREADY"):
                ready = True
                break
            # anything else (forwarded worker log lines — log_to_driver
            # is on by default) is noise: keep reading
        if not ready:
            p.kill()
    open(ready_file, "w").close()
    total = 0
    starts, ends = [], []
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                p.kill()
                continue
            for line in out.splitlines():
                if line.startswith("CLIENTJSON "):
                    r = json.loads(line[len("CLIENTJSON "):])
                    total += r["tasks"]
                    starts.append(r["start"])
                    ends.append(r["end"])
    finally:
        try:
            os.unlink(ready_file)
        except OSError:
            pass
    if total == 0 or not starts:
        raise RuntimeError("no concurrent client completed")
    return total / max(1e-9, max(ends) - min(starts))

def bench_trace_overhead(ray_tpu, n=1500, pairs=3):
    """Tracing cost phase: async task throughput with tracing fully
    sampled vs. disabled, as a percent throughput loss.  Only the
    driver's env needs toggling: the root sampling decision happens at
    submit time, and worker-side execute spans obey the propagated
    sampled flag, so RT_* in this process controls the whole pipeline.

    Protocol: alternate off/on measurement pairs and compare BEST-OF
    rates.  Machine-load noise on a shared box swings identical runs by
    ±30%+, far more than the effect being measured; best-of discards
    slow outliers symmetrically, so the reported number converges on
    the true per-task cost instead of whichever run got unlucky.
    Must stay < 5% at the default sampling ratio (tracing is on by
    default — its cost is a perf budget item like burst_async_per_s)."""
    @ray_tpu.remote
    def e():
        return b"ok"

    def measure():
        ray_tpu.get([e.remote() for _ in range(100)], timeout=60)  # warm
        t0 = time.perf_counter()
        ray_tpu.get([e.remote() for _ in range(n)], timeout=120)
        return n / (time.perf_counter() - t0)

    saved = {k: os.environ.get(k)
             for k in ("RT_TRACING_ENABLED", "RT_TRACE_SAMPLING_RATIO")}
    on_rates, off_rates = [], []
    try:
        for _ in range(pairs):
            os.environ["RT_TRACING_ENABLED"] = "false"
            time.sleep(0.3)  # let the tracing config TTL cache refresh
            off_rates.append(measure())
            os.environ["RT_TRACING_ENABLED"] = "true"
            os.environ["RT_TRACE_SAMPLING_RATIO"] = "1.0"
            time.sleep(0.3)
            on_rates.append(measure())
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    on, off = max(on_rates), max(off_rates)
    return {
        "traced_async_per_s": round(on, 1),
        "untraced_async_per_s": round(off, 1),
        # negative = tracing measured faster (noise); report as-is
        "trace_overhead_pct": round(100.0 * (off - on) / off, 2),
    }

def bench_profile_overhead(ray_tpu, n=1200, pairs=2):
    """Sampling-profiler cost phase: async task throughput with the
    in-process sampler running at the default hz on the DRIVER (the
    submit hot path — the process an operator would actually profile
    while hunting the tasks/s plateau) vs. not running, as a percent
    throughput loss.  BEST-OF alternating pairs per the slow-box
    protocol, same as trace_overhead.  Budget: < 5% at
    profiler_default_hz — the profiler must be cheap enough to switch
    on against a production incident."""
    from ray_tpu._private import profiling

    @ray_tpu.remote
    def e():
        return b"ok"

    def measure():
        ray_tpu.get([e.remote() for _ in range(100)], timeout=60)  # warm
        t0 = time.perf_counter()
        ray_tpu.get([e.remote() for _ in range(n)], timeout=120)
        return n / (time.perf_counter() - t0)

    on_rates, off_rates = [], []
    for _ in range(pairs):
        off_rates.append(measure())
        started = profiling.start_sampler()
        try:
            on_rates.append(measure())
        finally:
            if started.get("ok"):
                profiling.stop_sampler()
    on, off = max(on_rates), max(off_rates)
    return {
        "profiled_async_per_s": round(on, 1),
        "unprofiled_async_per_s": round(off, 1),
        # negative = profiler measured faster (noise); report as-is
        "profile_overhead_pct": round(100.0 * (off - on) / off, 2),
    }

def bench_memory_scan_overhead(ray_tpu, n=2500, pairs=2, live_objects=10_000):
    """Memory-accounting cost phase: async task throughput while the
    head's periodic leak scan (running at its DEFAULT cadence) joins a
    10k-entry driver reference table every interval, vs the same scan
    over an emptied table.  The differential is what `rtpu memory`
    accounting costs a busy owner: each scan serves rpc_memory_summary
    off the driver's IO loop (10k ref records built under the ref-table
    lock) plus the agent/worker fan-out.  BEST-OF alternating pairs per
    the slow-box protocol (see bench_trace_overhead).  Budget:
    memory_scan_overhead_pct < 5."""
    @ray_tpu.remote
    def e():
        return b"ok"

    # every measured window must SPAN the scan cadence, or best-of
    # selection just picks whichever run dodged the scans entirely
    from ray_tpu._private.config import config as _cfg
    min_window = 1.2 * float(_cfg.memory_scan_interval_s)

    def measure():
        ray_tpu.get([e.remote() for _ in range(100)], timeout=60)  # warm
        t0 = time.perf_counter()
        done = 0
        while True:
            ray_tpu.get([e.remote() for _ in range(n)], timeout=120)
            done += n
            elapsed = time.perf_counter() - t0
            if elapsed >= min_window:
                return done / elapsed

    on_rates, off_rates = [], []
    for _ in range(pairs):
        off_rates.append(measure())
        live = [ray_tpu.put(i) for i in range(live_objects)]
        try:
            on_rates.append(measure())
        finally:
            del live  # refs release; the table shrinks back
    on, off = max(on_rates), max(off_rates)
    return {
        "scan_loaded_async_per_s": round(on, 1),
        "scan_unloaded_async_per_s": round(off, 1),
        # negative = loaded measured faster (noise); report as-is
        "memory_scan_overhead_pct": round(100.0 * (off - on) / off, 2),
    }


def _serve_http_get(host, port, conns, total, path, timeout_s=120):
    """Drive the Serve proxy with `conns` keep-alive connections issuing
    `total` GET requests between them; returns (rps, p99_ms)."""
    import asyncio

    lat = []
    errors = [0]
    counter = [0]

    async def client():
        try:
            reader, writer = await asyncio.open_connection(host, port)
        except OSError:
            errors[0] += 1
            return
        req = f"GET {path} HTTP/1.1\r\nHost: bench\r\n\r\n".encode()
        try:
            while counter[0] < total:
                counter[0] += 1
                t0 = time.perf_counter()
                writer.write(req)
                await writer.drain()
                status = await reader.readline()
                clen = 0
                while True:
                    h = await reader.readline()
                    if h in (b"\r\n", b"\n", b""):
                        break
                    if h.lower().startswith(b"content-length:"):
                        clen = int(h.split(b":", 1)[1])
                if clen:
                    await reader.readexactly(clen)
                if b"200" in status:
                    lat.append(time.perf_counter() - t0)
                else:
                    errors[0] += 1
        except (OSError, asyncio.IncompleteReadError):
            errors[0] += 1
        finally:
            try:
                writer.close()
            except Exception:
                pass

    async def run():
        await asyncio.wait_for(
            asyncio.gather(*[client() for _ in range(conns)]),
            timeout=timeout_s)

    t0 = time.perf_counter()
    asyncio.run(run())
    wall = time.perf_counter() - t0
    if not lat:
        raise RuntimeError(f"no serve responses ({errors[0]} errors)")
    lat.sort()
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1000.0
    return len(lat) / wall, p99

def _serve_sse_items(host, port, conns, rounds, path, timeout_s=120):
    """SSE items/s: each connection issues `rounds` back-to-back
    chunked requests on ONE keep-alive connection (exercising
    keep-alive-after-SSE, async plane only)."""
    import asyncio

    items = [0]

    async def client():
        reader, writer = await asyncio.open_connection(host, port)
        req = (f"GET {path} HTTP/1.1\r\nHost: bench\r\n"
               "Accept: text/event-stream\r\n\r\n").encode()
        try:
            for _ in range(rounds):
                writer.write(req)
                await writer.drain()
                while True:  # status + headers
                    h = await reader.readline()
                    if h in (b"\r\n", b"\n", b""):
                        break
                while True:  # chunks
                    size = int((await reader.readline()).strip() or b"0", 16)
                    if size == 0:
                        await reader.readline()  # trailing CRLF
                        break
                    await reader.readexactly(size + 2)  # data + CRLF
                    items[0] += 1
        finally:
            try:
                writer.close()
            except Exception:
                pass

    async def run():
        await asyncio.wait_for(
            asyncio.gather(*[client() for _ in range(conns)]),
            timeout=timeout_s)

    t0 = time.perf_counter()
    asyncio.run(run())
    if not items[0]:
        raise RuntimeError("no SSE items received")
    return items[0] / (time.perf_counter() - t0)

def bench_serve(ray_tpu, pairs=2, conns=64, total=1200):
    """Serve data-plane phases: keep-alive HTTP RPS + p99 through the
    proxy, async event-loop ingress vs the executor-thread baseline
    (legacy_threads=True), measured BEST-OF ALTERNATING PAIRS per the
    slow-box protocol.  Also: SSE streaming items/s and a 256-in-flight
    completion check (the old thread pool capped in-flight at ~32)."""
    from ray_tpu import serve

    @serve.deployment(name="echo_bench", num_replicas=2,
                      max_ongoing_requests=32)
    def echo_bench(x):
        return {"ok": 1}

    @serve.deployment(name="sse_bench")
    def sse_bench(x):
        for i in range(25):
            yield i

    serve.run(echo_bench.bind())
    serve.run(sse_bench.bind())
    out = {}
    try:
        thread_rates, async_rates, async_p99 = [], [], []
        for _ in range(pairs):
            for legacy in (True, False):
                try:
                    serve.shutdown_http()
                except Exception:
                    pass
                host, port = serve.start_http(legacy_threads=legacy)
                _serve_http_get(host, port, 4, 40, "/echo_bench?x=1")  # warm
                rps, p99 = _serve_http_get(host, port, conns, total,
                                           "/echo_bench?x=1")
                (thread_rates if legacy else async_rates).append(rps)
                if not legacy:
                    async_p99.append(p99)
        out["serve_rps"] = round(max(async_rates), 1)
        out["serve_rps_thread_baseline"] = round(max(thread_rates), 1)
        out["serve_async_vs_threads"] = round(
            max(async_rates) / max(thread_rates), 2)
        out["serve_p99_ms"] = round(min(async_p99), 2)
        # stream + high-inflight phases ride the async plane just started
        host, port = serve.proxy_addresses()[0]
        out["serve_stream_items_per_s"] = round(
            _serve_sse_items(host, port, 8, 3, "/sse_bench?x=1"), 1)
        rps256, _ = _serve_http_get(host, port, 256, 256, "/echo_bench?x=1")
        out["serve_inflight_256_ok"] = rps256 > 0
    finally:
        try:
            serve.shutdown_http()
        except Exception:
            pass
        for name in ("echo_bench", "sse_bench"):
            try:
                serve.delete(name)
            except Exception:
                pass
    return out

def bench_dag(ray_tpu, pairs=2, n=400, depth=8):
    """Compiled-graph phases: a 3-stage actor chain executed through the
    channel-compiled path (pinned actor loops over mutable shm channels,
    zero per-call task submission) vs the dynamic CompiledDAG baseline
    (real task submission per stage per execute), alternating pairs and
    reporting BEST-OF per the slow-box protocol.  The contract is
    `dag_vs_dynamic` >= 5x.  `dag_execute_p99_ms` comes from serial
    execute+get round trips on the compiled path."""
    from collections import deque

    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Stage:
        def step(self, x):
            return x + 1

    def build():
        with InputNode() as inp:
            out = inp
            for _ in range(3):
                out = Stage.bind().step.bind(out)
        return out

    def measure(use_channels):
        c = build().experimental_compile(max_in_flight=depth,
                                         use_channels=use_channels)
        get = (lambda ref: ref.get(timeout=60)) if use_channels \
            else (lambda ref: ray_tpu.get(ref, timeout=60))
        try:
            for _ in range(20):  # warm: leases/loops + channel attach
                get(c.execute(0))
            window = deque()  # keep `depth` executes in flight
            t0 = time.perf_counter()
            for i in range(n):
                if len(window) >= depth:
                    get(window.popleft())
                window.append(c.execute(i))
            while window:
                get(window.popleft())
            rate = n / (time.perf_counter() - t0)
            lats = []
            for i in range(200):
                t1 = time.perf_counter()
                get(c.execute(i))
                lats.append(time.perf_counter() - t1)
            lats.sort()
            p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))] * 1000.0
            return rate, p99
        finally:
            c.teardown()

    comp_rates, dyn_rates, comp_p99 = [], [], []
    for _ in range(pairs):
        for use_channels in (False, True):
            rate, p99 = measure(use_channels)
            if use_channels:
                comp_rates.append(rate)
                comp_p99.append(p99)
            else:
                dyn_rates.append(rate)
    best, base = max(comp_rates), max(dyn_rates)
    return {
        "dag_execute_per_s": round(best, 1),
        "dag_execute_dynamic_per_s": round(base, 1),
        "dag_vs_dynamic": round(best / base, 2),
        "dag_execute_p99_ms": round(min(comp_p99), 3),
    }

def bench_small_ops(ray_tpu, n=1000):
    """Small-object put/get ops/s (reference: ray_perf.py:120-122,
    'single client get/put' — 10,181.6 / 5,545.0 ops/s recorded)."""
    payload = b"x" * 100
    t0 = time.perf_counter()
    refs = [ray_tpu.put(payload) for _ in range(n)]
    put_rate = n / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    for r in refs:
        ray_tpu.get(r, timeout=60)
    get_rate = n / (time.perf_counter() - t0)
    return put_rate, get_rate

def bench_pg_churn(ray_tpu, n=40):
    """Placement group create+remove rate (reference:
    microbenchmark.json 'placement group create/removal' 796.6/s)."""
    from ray_tpu.util import placement_group, remove_placement_group

    t0 = time.perf_counter()
    for _ in range(n):
        pg = placement_group([{"CPU": 1}])
        pg.wait(timeout=30)
        remove_placement_group(pg)
    return n / (time.perf_counter() - t0)

def bench_put_gbps(ray_tpu, mb=100, iters=5):
    import numpy as np

    data = np.random.rand(mb * 1024 * 1024 // 8)
    refs = []
    t0 = time.perf_counter()
    for _ in range(iters):
        refs.append(ray_tpu.put(data))
    dt = time.perf_counter() - t0
    del refs
    return iters * mb / 1024 / dt

def bench_xfer(pairs=2, mb=256):
    """Bulk object-plane phase: two in-process node agents (plus a head)
    on one event loop; a `mb`-MB object is pulled cross-agent via the
    bulk transfer plane vs the legacy obj_chunk RPC path, alternating
    rpc/bulk pairs and reporting BEST-OF per the slow-box protocol (the
    ratio is the contract: bulk must be >= 3x the RPC baseline)."""
    import asyncio

    from ray_tpu._private.head import HeadService
    from ray_tpu._private.node_agent import NodeAgent

    size = mb * 1024 * 1024
    session = os.path.join("/tmp", f"rt-xferbench-{os.getpid()}")
    os.makedirs(session, exist_ok=True)
    payload = os.urandom(size)
    saved = os.environ.get("RT_OBJECT_TRANSFER_ENABLED")

    async def run():
        head = HeadService()
        head_port = await head.start()
        agents = []
        for i in range(2):
            ag = NodeAgent(("127.0.0.1", head_port), session, {"CPU": 1},
                           arena_path=os.path.join(session, f"arena-{i}"),
                           capacity=size + (64 << 20))
            await ag.start()
            agents.append(ag)
        a, b = agents
        rates = {"bulk": [], "rpc": []}
        try:
            for i in range(pairs):
                for plane in ("rpc", "bulk"):
                    os.environ["RT_OBJECT_TRANSFER_ENABLED"] = \
                        "true" if plane == "bulk" else "false"
                    oid = f"bench-{plane}-{i}"
                    loc = a.store.create(oid, size)
                    a.store.arena.view[
                        loc["offset"]:loc["offset"] + size] = payload
                    a.store.seal(oid)
                    t0 = time.perf_counter()
                    r = await asyncio.wait_for(
                        b.rpc_ensure_local(oid, src=[a.host, a.port]),
                        timeout=300)
                    dt = time.perf_counter() - t0
                    if not r.get("ok"):
                        raise RuntimeError(f"{plane} pull failed: {r}")
                    rates[plane].append(size / dt / 1e9)
                    # the puller's unpin is a oneway still in flight:
                    # wait it out so the freed arena space is reusable
                    # by the next round's create
                    for _ in range(200):
                        e = a.store.objects.get(oid)
                        if e is None or not e.pinned:
                            break
                        await asyncio.sleep(0.02)
                    b.store.free([oid])
                    a.store.free([oid])
        finally:
            for ag in agents:
                await ag.stop()
            await head.stop()
        return rates

    try:
        rates = asyncio.run(run())
    finally:
        if saved is None:
            os.environ.pop("RT_OBJECT_TRANSFER_ENABLED", None)
        else:
            os.environ["RT_OBJECT_TRANSFER_ENABLED"] = saved
    bulk, rpc = max(rates["bulk"]), max(rates["rpc"])
    return {
        "xfer_gb_per_s": round(bulk, 3),
        "xfer_rpc_baseline_gb_per_s": round(rpc, 3),
        "xfer_vs_rpc": round(bulk / rpc, 2),
    }

def _locality_bench(n=10):
    """Runs as a subprocess: 2-worker-node cluster, scatter `n` 2MB
    objects across them, then unconstrained gather tasks — reports the
    fraction routed to their argument's holder (and that held args were
    never transferred)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 2})
    cluster.add_node(num_cpus=2, resources={"s0": 1})
    cluster.add_node(num_cpus=2, resources={"s1": 1})
    ray_tpu.init(address=cluster.address)
    try:
        cluster.wait_for_nodes(3)
        import numpy as np

        @ray_tpu.remote
        def produce():
            import os as _os

            return _os.environ["RT_NODE_ID"], np.ones(
                300_000, dtype=np.float64)  # 2.4MB: plasma + directory

        @ray_tpu.remote
        def consume(pair):
            import os as _os

            holder, arr = pair
            return _os.environ["RT_NODE_ID"] == holder and arr.sum() > 0

        # scatter: pin producers alternately to the two worker nodes
        refs = []
        for i in range(n):
            shard = f"s{i % 2}"
            refs.append(produce.options(resources={shard: 0.01}).remote())
        ray_tpu.wait(refs, num_returns=len(refs), timeout=60)
        # gather: unconstrained consumers — locality should route each
        # to its argument's holder
        hits = ray_tpu.get([consume.remote(r) for r in refs], timeout=60)
        pct = 100.0 * sum(bool(h) for h in hits) / len(hits)
        print("LOCJSON " + json.dumps({"locality_hit_pct": round(pct, 1)}))
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()

def bench_locality_subprocess():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--locality-bench"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    for line in proc.stdout.splitlines():
        if line.startswith("LOCJSON "):
            return json.loads(line[len("LOCJSON "):])
    raise RuntimeError(
        f"locality bench rc={proc.returncode}: {proc.stderr[-400:]}")

def _chaos_bench(total_s=9.0, kill_at_s=2.5, conns=8):
    """Runs as a subprocess: 2 worker agents + a head node, steady Serve
    HTTP load, one agent SIGKILLed mid-run.  Reports availability (non-
    503/non-error success over the WHOLE run), post-kill p99 latency
    (the recovery tail), and how long the controller took to re-heal the
    replica set."""
    import asyncio
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 4})
    workers = [cluster.add_node(num_cpus=0, resources={"chaos": 2})
               for _ in range(2)]
    ray_tpu.init(address=cluster.address)
    try:
        cluster.wait_for_nodes(3)

        # replicas can only land on the two chaos nodes (the head node
        # has no "chaos" resource); SPREAD puts one on each
        @serve.deployment(name="chaos_echo", num_replicas=2,
                          max_ongoing_requests=32,
                          ray_actor_options={
                              "num_cpus": 0, "resources": {"chaos": 1},
                              "scheduling_strategy": "SPREAD"})
        def chaos_echo(x):
            return {"ok": 1}

        serve.run(chaos_echo.bind())
        host, port = serve.start_http()
        _serve_http_get(host, port, 4, 40, "/chaos_echo?x=1")  # warm

        # which agent hosts a replica? (kill one that actually does)
        actors = ray_tpu.api._worker().head.call("list_actors",
                                                 timeout=30)["actors"]
        replica_nodes = {a["node_id"] for a in actors
                         if a.get("name", "").startswith("serve:chaos_echo")}
        victim = next(w for w in workers if w.node_id in replica_nodes)

        results = []  # (t_start_rel, ok, latency_s)
        t0 = time.perf_counter()
        kill_done = [0.0]
        reheal_done = [0.0]

        def alive_replicas():
            actors = ray_tpu.api._worker().head.call("list_actors",
                                                     timeout=10)["actors"]
            return sum(1 for a in actors
                       if a.get("name", "").startswith("serve:chaos_echo")
                       and a["state"] == "ALIVE")

        def killer():
            time.sleep(kill_at_s)
            cluster.remove_node(victim)  # SIGKILL; workers die via PDEATHSIG
            kill_done[0] = time.perf_counter() - t0
            # re-heal is measured from ACTOR state at the head (the dead
            # replica goes DEAD the moment the node dies, the replacement
            # goes ALIVE when its constructor passes) — NOT from the
            # controller's replica-handle list, which swaps the dead
            # handle for the replacement in one reconcile round and so
            # never observably drops below 2
            dropped = False
            while time.perf_counter() - t0 < total_s + 20:
                try:
                    n = alive_replicas()
                    if not dropped and n < 2:
                        dropped = True
                    elif dropped and n >= 2:
                        reheal_done[0] = time.perf_counter() - t0
                        return
                except Exception:
                    pass
                time.sleep(0.1)

        async def client():
            req = (b"GET /chaos_echo?x=1 HTTP/1.1\r\nHost: bench\r\n\r\n")
            # reconnect-and-keep-counting: a severed connection records a
            # failure and the client RESUMES, so availability really is
            # measured over the whole run (a client that stopped at the
            # first break would freeze the denominator at kill time)
            while time.perf_counter() - t0 < total_s:
                try:
                    reader, writer = await asyncio.open_connection(host,
                                                                   port)
                except OSError:
                    results.append((time.perf_counter() - t0, False, 0.0))
                    await asyncio.sleep(0.05)
                    continue
                try:
                    while time.perf_counter() - t0 < total_s:
                        ts = time.perf_counter()
                        writer.write(req)
                        await writer.drain()
                        status = await reader.readline()
                        if not status:
                            # clean EOF: ONE failure for the break, then
                            # reconnect (writing to the dead socket would
                            # double-count it via the OSError path)
                            results.append((ts - t0, False, 0.0))
                            break
                        clen = 0
                        while True:
                            h = await reader.readline()
                            if h in (b"\r\n", b"\n", b""):
                                break
                            if h.lower().startswith(b"content-length:"):
                                clen = int(h.split(b":", 1)[1])
                        if clen:
                            await reader.readexactly(clen)
                        dt = time.perf_counter() - ts
                        results.append((ts - t0, b"200" in status, dt))
                except (OSError, asyncio.IncompleteReadError):
                    results.append((time.perf_counter() - t0, False, 0.0))
                finally:
                    try:
                        writer.close()
                    except Exception:
                        pass

        async def drive():
            await asyncio.wait_for(
                asyncio.gather(*[client() for _ in range(conns)],
                               return_exceptions=True),
                timeout=total_s + 60)

        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        asyncio.run(drive())
        kt.join(timeout=30)
        total = len(results)
        ok = sum(1 for _, good, _ in results if good)
        post_kill = sorted(dt for ts, good, dt in results
                           if good and ts >= kill_done[0] > 0)
        p99 = post_kill[min(len(post_kill) - 1,
                            int(0.99 * len(post_kill)))] if post_kill else 0.0
        out = {
            "chaos_requests_total": total,
            "chaos_availability_pct": round(100.0 * ok / max(total, 1), 2),
            "chaos_p99_recovery_s": round(p99, 4),
            "chaos_reheal_s": round(
                max(0.0, reheal_done[0] - kill_done[0]), 2)
            if reheal_done[0] else -1.0,
        }
        print("CHAOSJSON " + json.dumps(out))
    finally:
        try:
            serve.shutdown_http()
        except Exception:
            pass
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def _tail_bench(baseline_s=2.5, stall_s=3.0, post_s=6.0, conns=8):
    """Runs as a subprocess: 2 Serve replicas of an IDEMPOTENT echo
    deployment with p99-hedging, steady HTTP load, and one replica's
    worker chaos-STALLED (busy-hung, not killed — the gray failure)
    mid-run via the worker.stall site.  Contract: p99 over the stalled
    window stays within 2x the all-healthy baseline and ZERO requests
    fail — hedged duplicates absorb the requests that hit the gray
    replica and its circuit breaker evicts it from routing within a few
    hedge delays, instead of 3 health-probe periods."""
    import asyncio

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4, object_store_memory=96 * 1024 * 1024)
    try:
        class TailEcho:
            def __call__(self, x):
                return {"ok": 1}

            def wid(self):
                from ray_tpu._private.worker import global_worker_or_none

                return global_worker_or_none().worker_id

        serve.run(serve.deployment(
            TailEcho, name="tail_echo", num_replicas=2,
            max_ongoing_requests=32, idempotent=True,
            hedge_after_s="p99").bind())
        host, port = serve.start_http()
        _serve_http_get(host, port, 4, 50, "/tail_echo?x=1")  # warm

        w = ray_tpu.api._worker()
        replicas = [a for a in w.head.call("list_actors",
                                           timeout=30)["actors"]
                    if a.get("name", "").startswith("serve:tail_echo")
                    and a["state"] == "ALIVE"]
        victim_wid = ray_tpu.get(ray_tpu.get_actor(
            replicas[0]["name"]).handle_request.remote("wid", (), {}),
            timeout=30)

        results = []  # (t_rel, ok, latency_s)
        t0 = time.perf_counter()
        stall_at = [0.0]
        total_s = baseline_s + post_s

        async def injector():
            await asyncio.sleep(baseline_s)
            stall_at[0] = time.perf_counter() - t0
            w.head.call("chaos", op="inject",
                        rule={"site": "worker.stall", "action": "stall",
                              "target": victim_wid, "count": 1,
                              "delay_s": stall_s}, timeout=30)

        async def client():
            req = b"GET /tail_echo?x=1 HTTP/1.1\r\nHost: bench\r\n\r\n"
            while time.perf_counter() - t0 < total_s:
                try:
                    reader, writer = await asyncio.open_connection(host,
                                                                   port)
                except OSError:
                    results.append((time.perf_counter() - t0, False, 0.0))
                    await asyncio.sleep(0.05)
                    continue
                try:
                    while time.perf_counter() - t0 < total_s:
                        ts = time.perf_counter()
                        writer.write(req)
                        await writer.drain()
                        status = await reader.readline()
                        if not status:
                            results.append((ts - t0, False, 0.0))
                            break
                        clen = 0
                        while True:
                            h = await reader.readline()
                            if h in (b"\r\n", b"\n", b""):
                                break
                            if h.lower().startswith(b"content-length:"):
                                clen = int(h.split(b":", 1)[1])
                        if clen:
                            await reader.readexactly(clen)
                        dt = time.perf_counter() - ts
                        results.append((ts - t0, b"200" in status, dt))
                except (OSError, asyncio.IncompleteReadError):
                    results.append((time.perf_counter() - t0, False, 0.0))
                finally:
                    try:
                        writer.close()
                    except Exception:
                        pass

        async def drive():
            await asyncio.wait_for(
                asyncio.gather(injector(),
                               *[client() for _ in range(conns)],
                               return_exceptions=True),
                timeout=total_s + 60)

        asyncio.run(drive())
        # the contract is only meaningful if the stall actually fired:
        # a failed injection would measure healthy traffic twice and
        # report a vacuous pass.  Fired counts ride agent heartbeats to
        # the head (~3s period) — wait one out.
        deadline = time.perf_counter() + 15
        fired = 0
        while time.perf_counter() < deadline and not fired:
            st = w.head.call("chaos", op="status", timeout=30)
            fired = sum(int(r.get("fired", 0)) for r in st["rules"])
            if not fired:
                time.sleep(0.5)
        if not fired:
            raise RuntimeError("worker.stall rule never fired; the "
                               "tail numbers would be vacuous")

        def p99(vals):
            if not vals:
                return 0.0
            vals = sorted(vals)
            return vals[min(len(vals) - 1, int(0.99 * len(vals)))]

        # healthy = COMPLETED before the stall landed: a request still
        # in flight when the stall hit would smuggle multi-second
        # latencies into the baseline and make the <=2x ratio vacuous
        healthy = [dt for ts, ok, dt in results
                   if ok and stall_at[0] > 0 and ts + dt < stall_at[0]]
        stalled = [dt for ts, ok, dt in results
                   if ok and ts >= stall_at[0] > 0]
        failed = sum(1 for _ts, ok, _dt in results if not ok)
        base_p99, stall_p99 = p99(healthy), p99(stalled)
        out = {
            "tail_requests_total": len(results),
            "tail_failed_requests": failed,
            "tail_p99_healthy_ms": round(base_p99 * 1000, 2),
            "tail_p99_stalled_ms": round(stall_p99 * 1000, 2),
            # the acceptance ratio: <= 2.0 with zero failures means the
            # hedge + circuit breaker absorbed the gray replica
            "tail_p99_ratio": round(stall_p99 / max(base_p99, 1e-9), 2),
        }
        print("TAILJSON " + json.dumps(out))
    finally:
        try:
            serve.shutdown_http()
        except Exception:
            pass
        try:
            ray_tpu.shutdown()
        except Exception:
            pass


def bench_tail_subprocess():
    """Launch the tail-tolerance phase in a CPU-only subprocess
    (its own in-process cluster; the chaos stall must never touch the
    main bench cluster's workers)."""
    from __graft_entry__ import _clean_subprocess_env

    env = _clean_subprocess_env(1)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--tail-bench"], env=env, capture_output=True, text=True,
        timeout=300, cwd=REPO)
    for line in proc.stdout.splitlines():
        if line.startswith("TAILJSON "):
            return json.loads(line[len("TAILJSON "):])
    raise RuntimeError(
        f"tail bench rc={proc.returncode}: {proc.stderr[-400:]}")


def _autoscale_bench(total_s=18.0, conns=16):
    """Runs as a subprocess: a 1-node AutoscalingCluster (head only),
    Serve deployment with num_replicas="auto" whose replicas can only
    land on autoscaled worker nodes, ramped HTTP load.  The replica
    autoscaler scales on ongoing requests, replica infeasibility parks
    as PENDING-actor demand, the node autoscaler launches workers to
    resolve it, and when the load stops the fleet drains back through
    the graceful-drain state machine.  Reports availability over the
    WHOLE run (incl. both scale events), p99 latency, and the
    scale-up / drain latencies."""
    import asyncio
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import AutoscalingCluster

    cluster = AutoscalingCluster(
        head_resources={"CPU": 2},
        worker_node_types={
            "serve-worker": {"resources": {"CPU": 2}, "min_workers": 0,
                             "max_workers": 3}},
        idle_timeout_s=1.5, update_period_s=0.3)
    ray_tpu.init(address=cluster.address)
    try:
        @serve.deployment(name="auto_echo", num_replicas="auto",
                          max_ongoing_requests=32,
                          autoscaling_config={
                              "min_replicas": 1, "max_replicas": 3,
                              "target_ongoing_requests": 2,
                              "upscale_consecutive": 2,
                              # longer than any mid-load ongoing dip:
                              # the drain event the phase measures is
                              # the one AFTER the load stops
                              "downscale_delay_s": 8.0},
                          ray_actor_options={"num_cpus": 2})
        def auto_echo(x):
            time.sleep(0.02)  # enough service time to sustain ongoing
            return {"ok": 1}

        serve.run(auto_echo.bind())  # first replica = first node launch
        host, port = serve.start_http()
        _serve_http_get(host, port, 2, 20, "/auto_echo?x=1")  # warm

        results = []  # (t_rel, ok, latency_s)
        t0 = time.perf_counter()
        scale_up_done = [0.0]
        drain_done = [0.0]
        peak_nodes = [0]
        baseline_nodes = len(cluster.provider.non_terminated_nodes())

        def watcher():
            # scale-up latency: load start -> a SECOND worker node live;
            # drain latency: load stop -> fleet back at one node
            while time.perf_counter() - t0 < total_s + 90:
                n = len(cluster.provider.non_terminated_nodes())
                peak_nodes[0] = max(peak_nodes[0], n)
                tr = time.perf_counter() - t0
                if not scale_up_done[0] and n > baseline_nodes:
                    scale_up_done[0] = tr
                if tr > total_s and scale_up_done[0] \
                        and n <= baseline_nodes:
                    drain_done[0] = tr
                    return
                time.sleep(0.1)

        async def client():
            req = b"GET /auto_echo?x=1 HTTP/1.1\r\nHost: bench\r\n\r\n"
            while time.perf_counter() - t0 < total_s:
                try:
                    reader, writer = await asyncio.open_connection(host,
                                                                   port)
                except OSError:
                    results.append((time.perf_counter() - t0, False, 0.0))
                    await asyncio.sleep(0.05)
                    continue
                try:
                    while time.perf_counter() - t0 < total_s:
                        ts = time.perf_counter()
                        writer.write(req)
                        await writer.drain()
                        status = await reader.readline()
                        if not status:
                            results.append((ts - t0, False, 0.0))
                            break
                        clen = 0
                        while True:
                            h = await reader.readline()
                            if h in (b"\r\n", b"\n", b""):
                                break
                            if h.lower().startswith(b"content-length:"):
                                clen = int(h.split(b":", 1)[1])
                        if clen:
                            await reader.readexactly(clen)
                        results.append(
                            (ts - t0, b"200" in status,
                             time.perf_counter() - ts))
                except (OSError, asyncio.IncompleteReadError):
                    results.append((time.perf_counter() - t0, False, 0.0))
                finally:
                    try:
                        writer.close()
                    except Exception:
                        pass

        async def drive():
            await asyncio.wait_for(
                asyncio.gather(*[client() for _ in range(conns)],
                               return_exceptions=True),
                timeout=total_s + 60)

        wt = threading.Thread(target=watcher, daemon=True)
        wt.start()
        asyncio.run(drive())
        wt.join(timeout=120)
        total = len(results)
        ok = sum(1 for _, good, _ in results if good)
        lats = sorted(dt for _, good, dt in results if good and dt > 0)
        p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))] \
            if lats else 0.0
        out = {
            "autoscale_requests_total": total,
            "autoscale_availability_pct": round(
                100.0 * ok / max(total, 1), 2),
            "autoscale_p99_ms": round(p99 * 1000, 2),
            "scale_up_latency_s": round(scale_up_done[0], 2)
            if scale_up_done[0] else -1.0,
            "drain_latency_s": round(drain_done[0] - total_s, 2)
            if drain_done[0] else -1.0,
            # +1: the head node is not provider-managed
            "autoscale_peak_nodes": 1 + peak_nodes[0],
        }
        st = cluster.status()
        out["autoscale_scale_ups"] = st["scale_up_total"]
        out["autoscale_scale_downs"] = st["scale_down_total"]
        print("AUTOSCALEJSON " + json.dumps(out))
    finally:
        try:
            serve.shutdown_http()
        except Exception:
            pass
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def bench_autoscale_subprocess():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--autoscale-bench"],
        capture_output=True, text=True, timeout=420, cwd=REPO)
    for line in proc.stdout.splitlines():
        if line.startswith("AUTOSCALEJSON "):
            return json.loads(line[len("AUTOSCALEJSON "):])
    raise RuntimeError(
        f"autoscale bench rc={proc.returncode}: {proc.stderr[-400:]}")


def _oom_bench(n_tasks=60, alloc_mb=220, hold_s=0.25):
    """Runs as a subprocess: a head (0 CPUs) + 3 worker agents, each
    under a VIRTUAL 512MB memory envelope
    (memory_monitor_node_total_bytes — per-agent watchdog accounting
    sums only that agent's worker RSS, so several "nodes" on one host
    stay isolated and the real machine is never stressed).  The
    workload overcommits ~2x: two 220MB allocators per 512MB node push
    past the 0.85 threshold, the watchdog kills the ballooning worker
    with a typed receipt, and the owner's separate OOM budget retries
    with jittered backoff until pressure clears.  Contracts: ZERO agent
    deaths (the watchdog fires, never the kernel), >= 99% task success,
    and an always-OOM poison class quarantined within
    poison_task_threshold kills (typed PoisonedTaskError, not worker
    churn)."""
    MB = 1024 * 1024
    threshold = 5
    os.environ.update({
        "RT_MEMORY_MONITOR_NODE_TOTAL_BYTES": str(512 * MB),
        "RT_MEMORY_USAGE_THRESHOLD": "0.85",
        "RT_MEMORY_MONITOR_REFRESH_MS": "50",
        "RT_MEMORY_MONITOR_MIN_KILL_INTERVAL_MS": "150",
        "RT_TASK_OOM_RETRIES": "30",
        "RT_TASK_RETRY_DELAY_MS": "50",
        "RT_TASK_OOM_RETRY_MAX_BACKOFF_MS": "1000",
        "RT_POISON_TASK_THRESHOLD": str(threshold),
        "RT_POISON_TASK_TTL_S": "120",
    })
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 0})
    workers = [cluster.add_node(num_cpus=2) for _ in range(3)]
    ray_tpu.init(address=cluster.address)
    try:
        cluster.wait_for_nodes(4)

        @ray_tpu.remote(max_retries=0, name="oom_bench_alloc")
        def allocator(i):
            hoard = bytearray(alloc_mb * MB)
            for off in range(0, len(hoard), 4096):
                hoard[off] = 1  # touched pages: real RSS
            time.sleep(hold_s)
            return i

        t0 = time.perf_counter()
        refs = [allocator.remote(i) for i in range(n_tasks)]
        ok = 0
        failures = []
        for i, r in enumerate(refs):
            try:
                assert ray_tpu.get(r, timeout=300) == i
                ok += 1
            except Exception as exc:  # noqa: BLE001
                failures.append(f"{type(exc).__name__}: {exc}"[:120])
        wall = time.perf_counter() - t0

        # poison phase: a class that ALWAYS balloons past the threshold
        # and never finishes — must quarantine within `threshold` kills
        # instead of churning workers forever
        @ray_tpu.remote(max_retries=0, name="oom_bench_poison")
        def poison():
            hoard = bytearray(520 * MB)
            for off in range(0, len(hoard), 4096):
                hoard[off] = 1
            time.sleep(300)
            return len(hoard)

        poisoned_type = ""
        try:
            ray_tpu.get(poison.remote(), timeout=240)
        except Exception as exc:  # noqa: BLE001
            poisoned_type = type(exc).__name__
        head = ray_tpu.api._worker().head
        q = head.call("quarantine", op="list")["entries"]
        poison_entry = next(
            (e for e in q.values() if e["name"] == "oom_bench_poison"), {})
        agents_alive = sum(1 for w in workers if w.alive)
        out = {
            "oom_tasks_total": n_tasks,
            "oom_task_success_pct": round(100.0 * ok / n_tasks, 2),
            "oom_workload_wall_s": round(wall, 1),
            "oom_agents_alive": agents_alive,          # contract: 3
            "oom_poison_error": poisoned_type,         # PoisonedTaskError
            "oom_poison_kills": poison_entry.get("kills", -1),
            "oom_poison_quarantined": bool(
                poison_entry.get("quarantined")),
            "oom_failures": failures[:3],
        }
        print("OOMJSON " + json.dumps(out))
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def bench_oom_subprocess():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--oom-bench"],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    for line in proc.stdout.splitlines():
        if line.startswith("OOMJSON "):
            return json.loads(line[len("OOMJSON "):])
    raise RuntimeError(
        f"oom bench rc={proc.returncode}: {proc.stderr[-400:]}")


def bench_chaos_subprocess():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--chaos-bench"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    for line in proc.stdout.splitlines():
        if line.startswith("CHAOSJSON "):
            return json.loads(line[len("CHAOSJSON "):])
    raise RuntimeError(
        f"chaos bench rc={proc.returncode}: {proc.stderr[-400:]}")


def _pipeline_bench_loop():
    """MPMD pipeline bench body: runs in a CPU-only subprocess
    (its own in-process cluster + 2 stage actors), prints one JSON line.

    Best-of alternating pairs per the slow-box protocol: each round
    measures the single-program baseline THEN the 2-stage pipeline on
    the same global batch, so drift hits both sides equally.  Reports
    steady-state pp_tokens_per_s / pp_step_p99_ms / pipeline_bubble_pct
    and the single-program rate for the honest comparison (on one host
    the pipeline adds channel hops for no extra compute, so the ratio
    gauges overhead; on real multi-chip topologies pp multiplies the
    in-stage mesh instead)."""
    import numpy as np

    import ray_tpu
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.train.pipeline import TrainPipeline

    cfg = LlamaConfig.tiny()
    mb, m, seq, steps, pairs = 2, 4, 64, 8, 2
    B = mb * m
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(B, seq),
                          dtype=np.int32)

    def measure_sp():
        import jax

        from ray_tpu.parallel.mesh import MeshSpec, make_mesh, shard_batch
        from ray_tpu.train.gspmd import build_llama_train_state

        mesh = make_mesh(MeshSpec(dp=-1), devices=jax.devices()[:1])
        params, opt, step_fn, _ = build_llama_train_state(
            cfg, mesh, batch_size=B, seq_len=seq)
        toks = shard_batch(mesh, tokens)
        for _ in range(3):
            params, opt, loss = step_fn(params, opt, toks)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt, loss = step_fn(params, opt, toks)
        float(loss)
        return steps * B * seq / (time.perf_counter() - t0)

    def measure_pp():
        pipe = TrainPipeline(cfg, pp=2, microbatch_size=mb,
                             num_microbatches=m, seq_len=seq,
                             devices_per_stage=1, step_timeout=120.0)
        try:
            for _ in range(3):  # warm: stage jits + channel attach
                pipe.step(tokens)
            walls, bubbles = [], []
            for _ in range(steps):
                out = pipe.step(tokens)
                walls.append(out["wall_s"])
                bubbles.append(out["bubble_pct"])
            rate = steps * B * seq / sum(walls)
            return rate, walls, bubbles
        finally:
            pipe.teardown()

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    try:
        sp_rates, pp_rates = [], []
        all_walls, best_bubbles = [], []
        for _ in range(pairs):
            sp_rates.append(measure_sp())
            rate, walls, bubbles = measure_pp()
            if not pp_rates or rate > max(pp_rates):
                best_bubbles = bubbles
            pp_rates.append(rate)
            all_walls.extend(walls)
        all_walls.sort()
        p99 = all_walls[min(len(all_walls) - 1,
                            int(0.99 * len(all_walls)))] * 1000.0
        print("PIPEJSON " + json.dumps({
            "pp_tokens_per_s": round(max(pp_rates), 1),
            "pp_step_p99_ms": round(p99, 2),
            "pipeline_bubble_pct": round(
                sorted(best_bubbles)[len(best_bubbles) // 2], 2),
            "pp_single_program_tokens_per_s": round(max(sp_rates), 1),
        }))
    finally:
        ray_tpu.shutdown()


def bench_pipeline_subprocess():
    """Launch the pipeline bench in a CPU-only interpreter (the
    pp stages are actor subprocesses of ITS cluster, so the phase is
    tier-1-safe on CPU and never contends for the chip)."""
    from __graft_entry__ import _clean_subprocess_env

    env = _clean_subprocess_env(8)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--pipeline-bench"], env=env, capture_output=True, text=True,
        timeout=480, cwd=REPO)
    for line in proc.stdout.splitlines():
        if line.startswith("PIPEJSON "):
            return json.loads(line[len("PIPEJSON "):])
    raise RuntimeError(
        f"pipeline bench rc={proc.returncode}: {proc.stderr[-400:]}")


def main():
    sys.path.insert(0, REPO)
    import ray_tpu

    extras = {}
    errors = {}
    sync = 0.0

    def phase(name, fn):
        try:
            fn()
        except Exception as exc:  # noqa: BLE001
            errors[name] = f"{type(exc).__name__}: {exc}"[:300]

    started = False
    try:
        ray_tpu.init(num_cpus=max(4, os.cpu_count() or 4),
                     object_store_memory=1024 * 1024 * 1024)
        started = True
    except Exception as exc:  # noqa: BLE001
        errors["init"] = f"{type(exc).__name__}: {exc}"[:300]

    if started:
        def tasks_sync():
            nonlocal sync
            sync = bench_tasks_sync(ray_tpu)

        phase("tasks_sync", tasks_sync)
        phase("tasks_async", lambda: extras.__setitem__(
            "tasks_async_per_s", round(bench_tasks_async(ray_tpu), 1)))

        def actors():
            a_sync, a_async = bench_actor(ray_tpu)
            extras["actor_sync_per_s"] = round(a_sync, 1)
            extras["actor_async_per_s"] = round(a_async, 1)

        phase("actors", actors)

        def small_ops():
            p, g = bench_small_ops(ray_tpu)
            extras["put_small_per_s"] = round(p, 1)
            extras["get_small_per_s"] = round(g, 1)

        phase("small_ops", small_ops)
        phase("pg_churn", lambda: extras.__setitem__(
            "pg_create_remove_per_s", round(bench_pg_churn(ray_tpu), 1)))
        phase("put", lambda: extras.__setitem__(
            "put_gb_per_s", round(bench_put_gbps(ray_tpu), 2)))
        phase("dag", lambda: extras.update(bench_dag(ray_tpu)))
        # burst-sequence + multi-client phases LAST among task phases:
        # the sync burst is deliberate history pollution, and proving the
        # earlier numbers unaffected by ordering is part of the contract
        phase("trace_overhead", lambda: extras.update(
            bench_trace_overhead(ray_tpu)))
        phase("profile_overhead", lambda: extras.update(
            bench_profile_overhead(ray_tpu)))
        phase("memory_scan_overhead", lambda: extras.update(
            bench_memory_scan_overhead(ray_tpu)))
        phase("burst_async", lambda: extras.__setitem__(
            "burst_async_per_s", round(bench_burst_then_async(ray_tpu), 1)))
        phase("head_scaling", lambda: extras.update(
            bench_head_scaling(ray_tpu)))
        # single-client async AFTER the multi-client storm: residue from
        # eight drivers' worth of leases/events must not depress a fresh
        # burst (the multi-client cousin of burst_async_per_s)
        phase("post_scaleout_async", lambda: extras.__setitem__(
            "post_scaleout_async_per_s",
            round(bench_tasks_async(ray_tpu), 1)))
        # serve phases after the task phases: a serve regression (proxy
        # wedge, deploy failure) can never zero out the numbers above —
        # phase() catches it and the internal asyncio drivers carry
        # their own hard timeouts
        phase("serve", lambda: extras.update(bench_serve(ray_tpu)))
        try:
            ray_tpu.shutdown()
        except Exception as exc:  # noqa: BLE001
            errors["shutdown"] = f"{type(exc).__name__}: {exc}"[:300]

    # head scale-out A/B control: the same 2/8-client ladder against a
    # single-loop head (head_ingest_shards=0) in its own subprocess
    # cluster, after shutdown so both sides of the comparison owned the
    # whole box; the sharded side is the head_scaling phase above
    phase("head_scaling_single_loop", lambda: extras.update(
        bench_head_scaling_single_loop_ab()))
    if extras.get("scaling_efficiency_pct_single_loop"):
        extras["scaling_efficiency_vs_single_loop_x"] = round(
            extras.get("scaling_efficiency_pct", 0.0)
            / extras["scaling_efficiency_pct_single_loop"], 2)

    # post-shutdown phases: the object-plane pair runs its own
    # in-process agents and the locality workload its own subprocess
    # cluster — neither shares state with the main cluster above
    phase("xfer", lambda: extras.update(bench_xfer()))
    phase("locality", lambda: extras.update(bench_locality_subprocess()))
    # chaos_recovery: SIGKILL one of two agents under steady Serve load;
    # contract: chaos_availability_pct >= 99 (handle-level dead-replica
    # retry keeps clients whole while the controller re-heals)
    phase("chaos_recovery", lambda: extras.update(bench_chaos_subprocess()))
    # tail_tolerance: chaos-stall one of two Serve replicas under load;
    # contract: tail_p99_ratio <= 2.0 (stalled-window p99 vs healthy
    # baseline) with tail_failed_requests == 0 — hedging + the circuit
    # breaker absorb the gray replica
    phase("tail_tolerance", lambda: extras.update(bench_tail_subprocess()))
    # autoscale: ramp Serve HTTP load against a 1-node autoscaling
    # cluster; contract: autoscale_availability_pct >= 99 through both
    # the scale-up and the drain-based scale-down event
    phase("autoscale", lambda: extras.update(bench_autoscale_subprocess()))
    # oom_resilience: 3 virtual-envelope nodes, a workload overcommitting
    # node memory ~2x; contracts: zero agent deaths (watchdog kills, not
    # the kernel), >= 99% task success via the separate OOM retry
    # budget, and a poison class quarantined within
    # poison_task_threshold kills with a typed error
    phase("oom_resilience", lambda: extras.update(bench_oom_subprocess()))

    # pipeline phase: CPU-only subprocess cluster (2 MPMD stages over
    # channels vs the single-program baseline, best-of alternating pairs)
    phase("pipeline", lambda: extras.update(bench_pipeline_subprocess()))

    if errors:
        extras["errors"] = errors
    print(json.dumps({
        "metric": "single-client sync tasks/s (ray_perf.py:174 equivalent)",
        "value": round(sync, 1),
        "unit": "tasks/s",
        "vs_baseline": round(sync / 1006.9, 3),
        "extras": extras,
    }))
    if errors:
        sys.exit(1)


if __name__ == "__main__":
    if "--pipeline-bench" in sys.argv:
        sys.path.insert(0, REPO)
        _pipeline_bench_loop()
    elif "--locality-bench" in sys.argv:
        sys.path.insert(0, REPO)
        _locality_bench()
    elif "--chaos-bench" in sys.argv:
        sys.path.insert(0, REPO)
        _chaos_bench()
    elif "--tail-bench" in sys.argv:
        sys.path.insert(0, REPO)
        _tail_bench()
    elif "--autoscale-bench" in sys.argv:
        sys.path.insert(0, REPO)
        _autoscale_bench()
    elif "--oom-bench" in sys.argv:
        sys.path.insert(0, REPO)
        _oom_bench()
    elif "--head-scaling-bench" in sys.argv:
        sys.path.insert(0, REPO)
        i = sys.argv.index("--head-scaling-bench")
        _head_scaling_ab_bench(int(sys.argv[i + 1]))
    elif "--client-bench" in sys.argv:
        sys.path.insert(0, REPO)
        i = sys.argv.index("--client-bench")
        _client_bench(sys.argv[i + 1], int(sys.argv[i + 2]),
                      sys.argv[i + 3] if len(sys.argv) > i + 3 else "")
    else:
        main()
