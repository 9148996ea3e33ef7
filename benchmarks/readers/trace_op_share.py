"""Device time of the operations whose name (or long name) matches
`pattern`, as a share of the device's busy time in the traced window, in
percent.  Nothing when there is no trace or nothing matches: the metric
is then left out of the line."""

from benchmarks import trace_reduce


def read(obs, params):
    trace = obs.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    return 100.0 * secs / trace["busy_s"] if secs else None
