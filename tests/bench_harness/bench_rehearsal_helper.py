"""Runs `benchmarks/run.py` as the driver does, in a process of its own,
and splits what it printed."""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def left_running(marker: str):
    """Command lines of live processes that mention `marker`."""
    found = []
    for pid in os.listdir("/proc"):
        if pid.isdigit():
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode("utf-8",
                                                                "replace")
            except OSError:
                continue
            if marker in cmd:
                found.append(cmd)
    return found


def run_cell(*args, timeout=280):
    """One run, in a session directory of its own: whatever the run
    started (head, node agent, workers) carries that directory in its
    command line, and none of it may outlive the run."""
    session = tempfile.mkdtemp(prefix="bench-rt-")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               RT_TMPDIR=session)
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)
    deadline = time.monotonic() + 10
    while left_running(session) and time.monotonic() < deadline:
        time.sleep(0.2)
    assert left_running(session) == [], "the run left processes behind"
    shutil.rmtree(session, ignore_errors=True)
    lines = proc.stdout.splitlines()
    said = {}
    for line in lines:
        if line.startswith("bench "):
            _, what, payload = line.split(" ", 2)
            said[what] = json.loads(payload)
    results = [ln for ln in lines if ln.startswith("{")]
    return proc, said, results


def rehearse(workload, trace, seconds="3"):
    """`--rehearse`: exit 3, no result line, what would have been one."""
    proc, said, results = run_cell(
        "--workload", workload, "--seed", "2147483659", "--seconds", seconds,
        "--trace", str(trace), "--rehearse")
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert results == [], "a rehearsal printed a result line"
    would = said["rehearsal"]["would_print"]
    assert set(would) >= {"correct", "attempted", "failed", "metrics",
                          "device"}
    assert would["correct"] is True, said.get("incorrect")
    assert would["device"]["platform"] == "cpu"   # and so it is no result
    return said, would
