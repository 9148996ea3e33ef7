"""`decode_hbm_bound` for the hybrid expert family: the time the decode
pass's bytes need at the chip's peak memory bandwidth over the time the
pass takes, in percent.

Bytes (`model_math_qwen3next.decode_step_bytes`): the weights outside the
routed experts as stored (mixers, routers, shared experts, this share's
half of the head), the matrices of the experts a decode pass TOUCHED
(change of `moe_expert_calls_total.decode` over the change of
`decode_steps`, all layers of a pass together), the keys and values of
the live contexts — mean occupied lanes (polled) times the mean context a
request holds half-way through its answer — and each live (lane, linear
layer) state row read AND written in float32 (change of
`state_decode_rows_total` over that of `decode_steps`).  Nothing where
the program has no such counters."""

from benchmarks import model_math_qwen3next, peaks
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
    calls = change(polls, ["moe_expert_calls_total.decode"])
    rows = change(polls, ["state_decode_rows_total"])
    steps = change(polls, ["decode_steps"])
    if calls is None or rows is None or not steps:
        return None
    m, engine = obs["model"], obs["engine"]
    lanes = occupancy / 100.0 * polls[0][0]["max_batch"]
    n_bytes = model_math_qwen3next.decode_step_bytes(
        m, weight_itemsize=engine["param_bytes"]
        / model_math_qwen3next.total_params(m),
        kv_itemsize=KV_ITEMSIZE[engine["dtype"]],
        contexts=[lanes * summary["mean_context"]],
        experts_touched=calls / steps, state_rows=rows / steps)
    floor_s = n_bytes / peaks.peak(obs["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / (step_ms / 1000.0)
