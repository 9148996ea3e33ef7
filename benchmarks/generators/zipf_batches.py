"""Training input whose token frequencies are skewed: every step a fresh
batch of ids drawn by RANK from a Zipf distribution over the vocabulary
rows held (p(rank r) ~ r^-exponent), rank -> id by ONE permutation made
from the seed, on the host: the ids that are heavy stay heavy for the
whole run, as a corpus's do.

What that does to the runs of a cell: half of a batch's mass is some 70
ids (1 / sum p^2 at exponent 1.0 over 24576), and which experts those
ids' rows choose is one draw of random weights, so the share of a step's
assignments that lands on one chip's experts is one draw a seed (23 to
29 % on 15 seeds where a quarter is meant) — unless the experts are
placed on the chips by load, as `kinds/train_mellum.py` places them; and
because the heavy ids stay, a router that learns at all learns THEM
within the window (PERF.md section 6, PR 32, has both readings).

Parameters (the mix's JSON file): `batch`, `seq_len`, `zipf_exponent`.
Real token frequencies are skewed; uniform ids (`token_batches.py`) load
every expert of a routed layer alike, and it is the skew that makes the
dropless row buffer, the padding of its row tiles and the fullest
expert's load do work.  One packed sequence a row, no document
boundaries.  Returns the parameters a train loop needs to make the
batches itself, step by step, inside its timed loop.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Dict


def generate(params: Dict[str, Any], seed: int, seconds: float,
             vocab_size: int) -> Dict[str, Any]:
    return {"window_s": float(seconds), "seed": int(seed),
            "batch": int(params["batch"]), "seq_len": int(params["seq_len"]),
            "vocab_size": int(vocab_size),
            "zipf_exponent": float(params["zipf_exponent"])}


@lru_cache(maxsize=4)
def _cdf(vocab_size: int, exponent: float):
    """Cumulative probability by rank."""
    import numpy as np

    weight = np.arange(1, vocab_size + 1, dtype=np.float64) ** -exponent
    return np.cumsum(weight) / np.sum(weight)


@lru_cache(maxsize=4)
def _ids_by_rank(seed: int, vocab_size: int):
    """The seed's permutation: the id of each rank.  (Three words of
    entropy, the last not zero: no step's two are the same stream.)"""
    import numpy as np

    return np.random.default_rng([seed, 0, 1]).permutation(
        vocab_size).astype(np.int32)


def batch_for_step(plan: Dict[str, Any], step: int):
    """The token ids of step `step`: [batch, seq_len] int32, the same for
    the same seed and step wherever it is made."""
    import numpy as np

    vocab = plan["vocab_size"]
    rng = np.random.default_rng([plan["seed"], step])
    ranks = np.searchsorted(
        _cdf(vocab, plan["zipf_exponent"]),
        rng.random((plan["batch"], plan["seq_len"])), side="right")
    return _ids_by_rank(plan["seed"], vocab)[np.minimum(ranks, vocab - 1)]
