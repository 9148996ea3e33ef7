"""How close the gated-delta-rule decode kernel runs to the least time
the chip could take for it, in percent.

The least time: the bytes that `model_math_olmo.state_update_cost`
reckons for the state rows the decode passes updated INSIDE the traced
span (each read once and written once in float32, at its SHAPE's bytes
whatever the stored layout pads) at the chip's peak HBM bandwidth — the
bytes bound it: a row's 3.9 M float32 operations are the vector unit's
— over the device seconds of the operations labelled
`gated_delta_update` (the `pallas_call`'s name) in the same span.  The
rows are the change of `state_decode_rows_total` between the engine's
`stats()` just after the profiler started and just before it stopped
(`replica_laguna.py`).  Nothing where the program has no such counter
or the trace no such operation."""

from benchmarks import model_math_olmo, peaks, trace_reduce
from benchmarks.readers.gdn_chunk_roofline import span_change


def read(obs, params):
    trace = obs.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    rows = span_change(trace, "state_decode_rows_total")
    if not secs or not rows:
        return None
    cost = model_math_olmo.state_update_cost(obs["model"], rows)
    floor_s = cost["bytes"] / peaks.peak(obs["device"]["kind"],
                                         "hbm_bytes_per_s")
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
