"""Model FLOP/s utilisation of a Mellum-family train step, in percent:
the operations the forward and backward passes REQUIRE for a token of
this chip's share (`model_math_mellum.train_flops_per_token`: the held
heads' projections, attention's products over the band a layer lets a
query see, the held experts a token's routing lands on, the router, the
held head columns; nothing rematerialised, no padding row) times tokens
a second, over the chips' peak bfloat16 rate.  The held assignments a
token are the window's own count (the step's
`train_moe_assignments_total` over its layer passes and tokens), the
router's even spread where the program hands out no counters.  Tokens a
second over the steps in which the profiler neither started nor
stopped."""

from benchmarks import model_math_mellum, peaks


def read(obs, params):
    t = obs.get("train") or {}
    steps = t.get("clean_step_s")
    if not steps:
        return None
    model = obs["model"]
    counters = t.get("counters") or {}
    passes = counters.get("train_moe_layer_passes_total")
    if passes:
        held = (counters["train_moe_assignments_total"]
                / (passes * t["tokens_per_step"]))
    else:
        held = model_math_mellum.expected_held_assignments_per_token(model)
    tokens_per_s = t["tokens_per_step"] * len(steps) / sum(steps)
    flops = model_math_mellum.train_flops_per_token(
        model, t["seq_len"], held)
    peak = peaks.peak(obs["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * flops * tokens_per_s / (t["chips"] * peak)
