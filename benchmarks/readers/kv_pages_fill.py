"""Mean share of the full kind's pages that sequences hold
(`used_pages / (used_pages + free_pages)` of `stats()`, polled every
half second inside the window), all replicas, in percent.  A sequence
takes the pages of its whole prompt and answer at admission, so this is
how near the head of the queue is to waiting for pages to recycle.
Nothing where the polls have no such gauges."""


def read(obs, params):
    shares = [100.0 * s["used_pages"] / (s["used_pages"] + s["free_pages"])
              for rows in obs.get("polls") or [] for s in rows
              if s.get("used_pages") is not None
              and s.get("free_pages") is not None
              and s["used_pages"] + s["free_pages"]]
    return sum(shares) / len(shares) if shares else None
