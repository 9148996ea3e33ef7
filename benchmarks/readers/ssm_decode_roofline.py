"""How close the state-update decode kernel runs to the least time the
chip could take for it, in percent.

The least time: the bytes that `model_math_granite.state_update_cost`
reckons for the state rows the decode passes updated INSIDE the traced
span (each read once and written once in float32) at the chip's peak
HBM bandwidth — the bytes bound it: a row's 2.6 M float32 operations
are the vector unit's, a hundredth of its bytes' time — over the device
seconds of the operations labelled `ssm_state_update` (the
`pallas_call`'s name) in the same span.  The rows are the change of
`state_decode_rows_total` between the engine's `stats()` just after the
profiler started and just before it stopped (`replica_laguna.py`).  The
counter moves when a pass is dispatched, the trace when it runs, a step
later: the two differ by the passes in flight at the span's ends, one in
some hundreds.  Nothing where the program has no such counter or the
trace no such operation."""

from benchmarks import model_math_granite, peaks, trace_reduce
from benchmarks.readers.stats_ratio import lookup


def span_rows(trace):
    total = 0.0
    for pair in trace.get("span_stats") or [None]:
        if not pair:
            return None
        first = lookup(pair[0], "state_decode_rows_total")
        last = lookup(pair[1], "state_decode_rows_total")
        if first is None or last is None:
            return None
        total += last - first
    return total


def read(obs, params):
    trace = obs.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    rows = span_rows(trace)
    if not secs or not rows:
        return None
    cost = model_math_granite.state_update_cost(obs["model"], rows)
    floor_s = cost["bytes"] / peaks.peak(obs["device"]["kind"],
                                         "hbm_bytes_per_s")
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
