"""Mean share of the engine's decode lanes that hold a sequence
(`active / max_batch` of `stats()`, polled every half second inside the
window), all replicas, in percent."""


def read(obs, params):
    shares = [100.0 * s["active"] / s["max_batch"]
              for rows in obs.get("polls") or [] for s in rows]
    return sum(shares) / len(shares) if shares else None
