"""Model FLOP/s utilisation of the train step, in percent: the operations
the forward and backward passes REQUIRE for a token
(`model_math.train_flops_per_token`: 6 for each block and head matmul
parameter, the causal attention products; no embedding lookup, nothing
rematerialised) times tokens a second, over the chips' peak bfloat16 rate
from the table keyed by `device_kind`.  Tokens a second over the steps in
which the profiler neither started nor stopped."""

from benchmarks import model_math, peaks


def read(obs, params):
    t = obs.get("train") or {}
    steps = t.get("clean_step_s")
    if not steps:
        return None
    tokens_per_s = t["tokens_per_step"] * len(steps) / sum(steps)
    flops = model_math.train_flops_per_token(obs["model"], t["seq_len"])
    peak = peaks.peak(obs["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * flops * tokens_per_s / (t["chips"] * peak)
