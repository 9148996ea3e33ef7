"""Mean share of the state pool's slots that hold a sequence
(`state_slots_in_use / max_batch` of `stats()`, polled every half second
inside the window), all replicas, in percent.  A slot is held from
admission to the sequence's end, prefill included, so this lies above
the decoding lanes' share.  Nothing where the program has no such
gauge."""


def read(obs, params):
    shares = [100.0 * s["state_slots_in_use"] / s["max_batch"]
              for rows in obs.get("polls") or [] for s in rows
              if "state_slots_in_use" in s]
    return sum(shares) / len(shares) if shares else None
