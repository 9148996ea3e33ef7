"""`decode_hbm_bound` for the hybrid linear-attention family: the time
the decode pass's bytes need at the chip's peak memory bandwidth over
the time the pass takes, in percent.

Bytes (`model_math_olmo.decode_step_bytes`): every weight a pass reads
as stored (all but the embedding table), the keys and values of the
live contexts at the model's 30 heads — mean occupied lanes (polled)
times the mean context a request holds half-way through its answer, as
`decode_hbm_bound` reckons them — and each live (lane, linear layer)
row read AND written in float32: the change of
`state_decode_rows_total` over the change of `decode_steps`.  Nothing
where the program has no such counter."""

from benchmarks import model_math_olmo, peaks
from benchmarks.readers import engine_decode_step, engine_occupancy
from benchmarks.readers.stats_ratio import change

KV_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(obs, params):
    step_ms = engine_decode_step.read(obs, {})
    occupancy = engine_occupancy.read(obs, {})
    summary = obs.get("summary") or {}
    polls = [rows for rows in obs.get("polls") or [] if len(rows) >= 2]
    if not step_ms or occupancy is None or not polls \
            or not summary.get("mean_context"):
        return None
    rows = change(polls, ["state_decode_rows_total"])
    steps = change(polls, ["decode_steps"])
    if rows is None or not steps:
        return None
    m, engine = obs["model"], obs["engine"]
    lanes = occupancy / 100.0 * polls[0][0]["max_batch"]
    n_bytes = model_math_olmo.decode_step_bytes(
        m, weight_itemsize=engine["param_bytes"]
        / model_math_olmo.total_params(m),
        kv_itemsize=KV_ITEMSIZE[engine["dtype"]],
        contexts=[lanes * summary["mean_context"]],
        state_rows=rows / steps)
    floor_s = n_bytes / peaks.peak(obs["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / (step_ms / 1000.0)
