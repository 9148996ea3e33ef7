"""Pages the window layers do NOT hold, as a share of what a one-kind
cache would hold for them, in percent: a cache with one kind gives every
layer the pages of the full kind, so a window layer would hold
`kv_pages_in_use.full` where it holds `kv_pages_in_use.<window kind>`.
Sums of `LLMEngine.stats()`'s gauge over the polls inside the window,
all replicas.  Nothing where the program has no such gauge, the model no
window kind, or no page was held."""


def read(obs, params):
    full = held = 0.0
    for rows in obs.get("polls") or []:
        for s in rows:
            in_use = s.get("kv_pages_in_use")
            if not isinstance(in_use, dict) or len(in_use) < 2:
                return None
            full += in_use["full"] * (len(in_use) - 1)
            held += sum(v for k, v in in_use.items() if k != "full")
    return 100.0 * (1.0 - held / full) if full else None
