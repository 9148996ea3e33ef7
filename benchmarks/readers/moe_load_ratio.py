"""How uneven the routing is: the fullest expert's tokens over the mean
tokens of a touched expert, a layer pass — a ratio of two ratios of
`LLMEngine.stats()` counters over the window: (change of
`moe_max_load_total` / change of `moe_layer_passes_total`) over (change
of `moe_assignments_total` / change of `moe_expert_calls_total`).  1
when every touched expert has the same load.

Taken for the decode passes and for the prefill passes SEPARATELY and
then averaged by their assignments: a decode pass routes a few dozen
tokens and a prefill pass hundreds, so the four sums taken over both
kinds at once would divide the many small passes' maxima by the few
large passes' means (ISSUE 28 asked for that; it reads under 1 on mixed
traffic, which no ratio of a maximum to a mean can).  Nothing where the
program has no such counters."""

from benchmarks.readers.stats_ratio import change

PASSES = ("decode", "prefill")


def read(obs, params):
    polls = [rows for rows in obs.get("polls") or [] if len(rows) >= 2]
    if not polls:
        return None
    total = weight = 0.0
    for which in PASSES:
        d = {key: change(polls, [f"moe_{key}_total.{which}"])
             for key in ("max_load", "layer_passes", "assignments",
                         "expert_calls")}
        if any(v is None for v in d.values()):
            return None
        if not d["layer_passes"] or not d["expert_calls"]:
            continue
        fullest = d["max_load"] / d["layer_passes"]
        mean = d["assignments"] / d["expert_calls"]
        total += d["assignments"] * fullest / mean
        weight += d["assignments"]
    return total / weight if weight else None
