"""Open-loop request traffic: arrivals on a schedule fixed before the
run, whatever the system does with them.

The window of every seed holds the SAME cycle of requests — the same
gaps between arrivals, prompt lengths and output lengths in the same
cyclic order — entered at another point (and with other token ids).  The
gaps are the quantiles of the exponential distribution at the mix's
rate, scaled to fill the window; the lengths are the quantiles of its
log-normal distributions; each list is shuffled once, the same way in
every run (`ORDER_SEED`).  `--seed` picks the point of the cycle at which
the window starts (unless the mix fixes it, `start_at`), and the lead-in
replays the stretch of the cycle that comes just before that point.  So a
seed changes neither how much work a window holds nor which request meets
which: every burst and every long prompt of the cycle lies in every run's
window, behind the requests that precede it in the cycle, and runs of
different seeds can be compared like runs of one.

This is NOT "Poisson arrivals drawn from --seed" (ISSUE 24's wording): it
is one realisation of such arrivals, with the distribution's quantiles
for its gaps and lengths, replayed by every seed.  The spread between
seeds is therefore the repeat noise of that one realisation, which is
what a bound on a regression needs; how the system does on another
draw of the same distribution no run of this mix says.

Parameters (the mix's JSON file):
    rate_rps            offered requests a second, all replicas together
    start_at            optional: the request of the cycle the window opens
                        on, for every seed.  A mix offered ABOVE capacity
                        sets it: its backlog grows, so a window serves only
                        the first part of what it is offered, and which part
                        of the cycle that is — how long its prompts are —
                        would otherwise depend on the seed (PERF.md, PR 24:
                        +-4 % in tokens/s between seeds, 0.1-2 % within one)
    lead_in_s           seconds of the same traffic before the window;
                        its requests are served and not counted
    prompt_len, output_len   {"median", "sigma", "min", "max"}: log-normal
                        with that median and log-standard-deviation,
                        clipped

No two prompts of a run share their first token, where real prompts all
start with BOS: see `generate`.  A mix of shared prefixes (a system
prompt, BOS) brings its parameter along with the cell that uses it, once
the engine's copy-on-write no longer kills it.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Any, Dict, List

ORDER_SEED = 0   # the one shuffle that fixes the cycle's order


def lognormal_lengths(n: int, spec: Dict[str, Any]) -> List[int]:
    """n lengths: the (i + 0.5) / n quantiles, clipped and rounded."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(round(min(max(x, spec["min"]), spec["max"]))))
    return out


def exponential_gaps(n: int, total_s: float) -> List[float]:
    """n gaps, the quantiles of an exponential distribution, scaled to
    sum to `total_s`."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total_s / sum(raw)
    return [g * scale for g in raw]


def generate(params: Dict[str, Any], seed: int, seconds: float,
             vocab_size: int, rate_scale: float = 1.0
             ) -> Dict[str, Any]:
    """Returns {"lead_in_s", "window_s", "requests": [...]}, each request
    {"rid", "due_s" (from the start of the lead-in), "tokens",
    "max_new_tokens", "counted" (due inside the window)}, by due time."""
    lead = float(params["lead_in_s"])
    window = float(seconds)
    rate = float(params["rate_rps"]) * rate_scale
    n = max(1, int(round(rate * window)))
    order = random.Random(ORDER_SEED)
    gaps = exponential_gaps(n, window)
    prompts = lognormal_lengths(n, params["prompt_len"])
    outputs = lognormal_lengths(n, params["output_len"])
    order.shuffle(gaps)
    order.shuffle(prompts)
    order.shuffle(outputs)
    # the cycle: request i at `at[i]` on a circle as long as the window
    at, t = [], 0.0
    for g in gaps:
        t += g
        at.append(t - g / 2.0)
    rnd = random.Random(int(seed))
    first = int(params["start_at"]) % n if "start_at" in params \
        else rnd.randrange(n)
    start = at[first] - 1e-9               # the window opens on a request

    # No two prompts of a run start with the same token.  The engine
    # shares KV pages among LIVE sequences down to one leading token
    # (copy-on-write), and that copy is eager today: it compiles inside
    # the window and copies every pool whole, which a pool that fills the
    # chip cannot afford (PERF.md, PR 24: RESOURCE_EXHAUSTED, the engine
    # loop dead, in 2 of 6 seeds).  Random ids collide once in a few
    # hundred requests.  Real prompts all start with BOS, so this is a
    # state real traffic cannot reach with this program at this memory
    # fill: a shared first token goes back in as soon as `_cow_copy` is
    # jitted and donated (PERF.md section 7, first of the program faults).
    firsts = iter(rnd.sample(range(1, vocab_size),
                             n * (1 + int(lead // window) + 1)))

    def request(i: int, rid: str, due: float, counted: bool):
        body = [next(firsts)] + [rnd.randrange(1, vocab_size)
                                 for _ in range(prompts[i] - 1)]
        return {"rid": rid, "due_s": due, "tokens": body,
                "max_new_tokens": outputs[i], "counted": counted}

    requests = []
    for i in range(n):
        ahead = (at[i] - start) % window      # into the window
        requests.append(request(i, f"s{int(seed)}-{i}", lead + ahead, True))
        back = window - ahead                 # before the window opens
        while back <= lead:                   # (a lead-in longer than the
            requests.append(request(         # cycle replays it again)
                i, f"s{int(seed)}-{i}-lead{int(back // window)}",
                lead - back, False))
            back += window
    requests.sort(key=lambda r: r["due_s"])
    return {"lead_in_s": lead, "window_s": window, "requests": requests}
