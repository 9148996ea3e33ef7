"""`decode_hbm_bound` for a Laguna-family model: the time the decode
pass's bytes need at the chip's peak memory bandwidth over the time the
pass takes, in percent.

Bytes (`model_math_laguna.decode_step_bytes`): the weights outside the
routed experts as stored, the matrices of the experts a decode pass
TOUCHED (change of `moe_expert_calls_total.decode` over the change of
`decode_steps`, all sparse layers of a pass together), and the keys and
values of the live contexts — mean occupied lanes (polled) at the mean
context a request holds half-way through its answer, a sliding layer
reading its window at most.  Nothing where the program has no such
counters."""

from benchmarks import model_math_laguna, peaks
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
    steps = change(polls, ["decode_steps"])
    if calls is None or not steps:
        return None
    m, engine = obs["model"], obs["engine"]
    lanes = occupancy / 100.0 * obs["polls"][0][0]["max_batch"]
    n_bytes = model_math_laguna.decode_step_bytes(
        m, weight_itemsize=engine["param_bytes"]
        / model_math_laguna.total_params(m),
        kv_itemsize=KV_ITEMSIZE[engine["dtype"]],
        contexts=[summary["mean_context"]] * max(1, round(lanes)),
        experts_touched=calls / steps)
    floor_s = n_bytes / peaks.peak(obs["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / (step_ms / 1000.0)
