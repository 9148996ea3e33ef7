"""Median milliseconds of a train step in the window: host clock from
before the step's batch is made to after `block_until_ready` on its
loss.  Steps during which the profiler started or stopped are left out."""

from benchmarks.stats import percentile


def read(obs, params):
    steps = (obs.get("train") or {}).get("clean_step_s")
    return 1000.0 * percentile(steps, 50) if steps else None
