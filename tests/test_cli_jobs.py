"""CLI + job submission tests.

Mirrors the reference's CLI and job-manager suites
(reference: python/ray/tests/test_cli.py,
dashboard/modules/job/tests/test_job_manager.py): a cluster stood up
entirely from the shell runs a submitted job to completion, with
status and logs retrievable from any client.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private.spawn import python_module_cmd


@pytest.fixture
def isolated_tmpdir(tmp_path, monkeypatch):
    monkeypatch.setenv("RT_TMPDIR", str(tmp_path))
    return str(tmp_path)


def _cli(args, tmpdir, timeout=120):
    cmd, env_up = python_module_cmd("ray_tpu.scripts", list(args))
    env = dict(os.environ)
    env.update(env_up)
    env["RT_TMPDIR"] = tmpdir
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_cli_start_status_job_stop(isolated_tmpdir):
    tmp = isolated_tmpdir
    r = _cli(["start", "--head", "--num-cpus", "4"], tmp)
    assert r.returncode == 0, r.stderr
    assert "cluster started at" in r.stdout
    try:
        r = _cli(["status"], tmp)
        assert r.returncode == 0, r.stderr
        assert "1 node(s)" in r.stdout

        script = os.path.join(tmp, "jobscript.py")
        with open(script, "w") as f:
            f.write(
                "import ray_tpu\n"
                "ray_tpu.init()\n"  # RT_ADDRESS from the supervisor
                "@ray_tpu.remote\n"
                "def sq(x):\n"
                "    return x * x\n"
                "print('job result:', ray_tpu.get("
                "[sq.remote(i) for i in range(4)], timeout=60))\n"
                "ray_tpu.shutdown()\n")
        r = _cli(["job", "submit", "--wait", "--",
                  sys.executable, script], tmp, timeout=240)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "SUCCEEDED" in r.stdout
        assert "job result: [0, 1, 4, 9]" in r.stdout

        r = _cli(["job", "list"], tmp)
        assert r.returncode == 0
        assert "SUCCEEDED" in r.stdout
    finally:
        r = _cli(["stop"], tmp)
    assert r.returncode == 0, r.stderr


def test_cli_worker_join(isolated_tmpdir):
    tmp = isolated_tmpdir
    r = _cli(["start", "--head", "--num-cpus", "2"], tmp)
    assert r.returncode == 0, r.stderr
    address = [ln for ln in r.stdout.splitlines()
               if "cluster started at" in ln][0].split()[-1]
    try:
        r = _cli(["start", "--address", address, "--num-cpus", "2",
                  "--resources", json.dumps({"extra": 1})], tmp)
        assert r.returncode == 0, r.stderr
        deadline = time.monotonic() + 30
        ok = False
        while time.monotonic() < deadline:
            r = _cli(["status"], tmp)
            if "2 node(s)" in r.stdout:
                ok = True
                break
            time.sleep(0.5)
        assert ok, r.stdout
    finally:
        _cli(["stop"], tmp)


def test_job_api_stop_and_logs(isolated_tmpdir):
    tmp = isolated_tmpdir
    r = _cli(["start", "--head", "--num-cpus", "4"], tmp)
    assert r.returncode == 0, r.stderr
    address = [ln for ln in r.stdout.splitlines()
               if "cluster started at" in ln][0].split()[-1]
    try:
        from ray_tpu.job_submission import JobSubmissionClient

        client = JobSubmissionClient(address)
        try:
            job_id = client.submit_job(
                f"{sys.executable} -c \"import time\n"
                "print('spinning', flush=True)\n"
                "time.sleep(600)\"")
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if client.get_job_status(job_id) == "RUNNING" \
                        and "spinning" in client.get_job_logs(job_id):
                    break
                time.sleep(0.3)
            assert client.get_job_status(job_id) == "RUNNING"
            client.stop_job(job_id)
            status = client.wait_until_finish(job_id, timeout=60)
            assert status == "STOPPED"
            assert "spinning" in client.get_job_logs(job_id)
        finally:
            client.close()
    finally:
        _cli(["stop"], tmp)
