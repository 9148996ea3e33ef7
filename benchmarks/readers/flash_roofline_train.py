"""How close a training step's flash-attention kernels of one direction
run to the least time the chip could take for them, in percent.

The least time: the larger of (bytes / peak HBM bandwidth) and
(operations / peak bfloat16 rate) that `model_math_mellum.flash_cost`
reckons for the sequences of the steps that ran WHOLLY inside the traced
span, in the layers of `params["which"]` ("window", "full" or "all"),
`params["backward"]` or forward: the products over the (query, key)
pairs a layer lets a query see — the band of a sliding layer — and
nothing a blockwise kernel recomputes or masks away.  A forward kernel
is asked twice a step where every block is rematerialised.  Over the
device seconds of the operations whose label matches
`params["pattern"]`.  The counted steps cover a little less than the
trace, never more.  Nothing where the run counted no step inside the
span or the trace has no such operation."""

from benchmarks import model_math_mellum, peaks, trace_reduce


def read(obs, params):
    trace = obs.get("trace") or {}
    t = obs.get("train") or {}
    if not trace.get("busy_s") or not t.get("span_steps"):
        return None
    secs = trace_reduce.seconds_matching(trace, params["pattern"])
    if not secs:
        return None
    backward = bool(params["backward"])
    asked = 1.0 if backward or not t.get("remat") else 2.0
    sequences = t["span_steps"] * t["tokens_per_step"] / t["seq_len"]
    cost = model_math_mellum.flash_cost(
        obs["model"], t["seq_len"], asked * sequences,
        which=params["which"], backward=backward)
    kind = obs["device"]["kind"]
    floor_s = max(cost["bytes"] / peaks.peak(kind, "hbm_bytes_per_s"),
                  cost["flops"] / peaks.peak(kind, "bf16_flops_per_s"))
    return 100.0 * floor_s / (secs * trace.get("devices", 1))
