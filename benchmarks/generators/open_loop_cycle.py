"""`open_loop` with a cycle whose LENGTH is the mix's, not the window's.

`generators/open_loop.py` makes its cycle of rate x window requests, so
a mix of few, uneven requests (a long-document mix at about one request
a second: 52 in a window of 50 s) judges its tails on a handful of one
realisation, and a sweep draws another realisation at every rate.  Here
the mix states `cycle_requests`: that many gaps and lengths — the same
quantiles, shuffled by the same `ORDER_SEED` — laid on a circle that
lasts `cycle_requests / rate` seconds.  A window replays the stretch of
the circle that starts at request `start_at`, for `seconds`; the lead-in
the stretch just before it.  So at ANY rate (`rate_scale`, a sweep's)
the requests and their order are the same and only the gaps shrink: a
faster window reaches further round the same circle from the same
request.  A window longer than the circle goes round again.

`--seed` draws no arrivals: it gives the token ids (and, in the kind,
the weights).  `start_at` is required, for the reason `open_loop` gives
for a mix with a backlog: with prompts this uneven the stretch a window
holds decides its load, and seeds are compared like runs of one.

Parameters: `open_loop`'s (`rate_rps`, `lead_in_s`, `prompt_len`,
`output_len`), and
    cycle_requests      the requests of the circle
    start_at            the request of the circle the window opens on
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict

from benchmarks.generators.open_loop import (ORDER_SEED, exponential_gaps,
                                             lognormal_lengths)


def cycle(params: Dict[str, Any], rate_scale: float = 1.0) -> Dict[str, Any]:
    """The circle: its `period_s`, each request's place `at` on it and
    its `prompts` and `outputs` lengths, in the circle's order."""
    n = int(params["cycle_requests"])
    period = n / (float(params["rate_rps"]) * rate_scale)
    order = random.Random(ORDER_SEED)
    gaps = exponential_gaps(n, period)
    prompts = lognormal_lengths(n, params["prompt_len"])
    outputs = lognormal_lengths(n, params["output_len"])
    order.shuffle(gaps)
    order.shuffle(prompts)
    order.shuffle(outputs)
    at, t = [], 0.0
    for g in gaps:
        t += g
        at.append(t - g / 2.0)
    return {"period_s": period, "at": at, "prompts": prompts,
            "outputs": outputs}


def generate(params: Dict[str, Any], seed: int, seconds: float,
             vocab_size: int, rate_scale: float = 1.0
             ) -> Dict[str, Any]:
    """`open_loop.generate`'s plan: {"lead_in_s", "window_s", "requests":
    [{"rid", "due_s" (from the start of the lead-in), "tokens",
    "max_new_tokens", "counted" (due inside the window)}, by due time]}."""
    lead, window = float(params["lead_in_s"]), float(seconds)
    c = cycle(params, rate_scale)
    period, at = c["period_s"], c["at"]
    n = len(at)
    start = at[int(params["start_at"]) % n] - 1e-9   # opens on a request
    due = []                    # (seconds from the window's start, i, turn)
    for i in range(n):
        ahead = (at[i] - start) % period
        for turn in range(-math.ceil(lead / period) - 1,
                          math.ceil(window / period) + 1):
            t = ahead + turn * period
            if -lead <= t < window:
                due.append((t, i, turn))
    due.sort()
    rnd = random.Random(int(seed))
    # no two prompts of a run start with the same token: `open_loop` has why
    firsts = rnd.sample(range(1, vocab_size), len(due))
    requests = []
    for first, (t, i, turn) in zip(firsts, due):
        body = [first] + [rnd.randrange(1, vocab_size)
                          for _ in range(c["prompts"][i] - 1)]
        # (a turn before the window's lies in the lead-in: t < 0)
        tag = "" if turn == 0 else \
            f"-lead{-turn}" if turn < 0 else f"-turn{turn}"
        requests.append({"rid": f"s{int(seed)}-{i}{tag}", "due_s": lead + t,
                         "tokens": body, "max_new_tokens": c["outputs"][i],
                         "counted": t >= 0})
    return {"lead_in_s": lead, "window_s": window, "requests": requests}
