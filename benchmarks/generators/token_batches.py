"""Training input: a fresh batch of token ids for every step, made on
the host from the seed.

Parameters (the mix's JSON file): `batch`, `seq_len`.  Returns the
parameters a train loop needs to make the batches itself, step by step,
inside its timed loop — there they cost what a host-fed input costs.
"""

from __future__ import annotations

from typing import Any, Dict


def generate(params: Dict[str, Any], seed: int, seconds: float,
             vocab_size: int) -> Dict[str, Any]:
    return {"window_s": float(seconds), "seed": int(seed),
            "batch": int(params["batch"]), "seq_len": int(params["seq_len"]),
            "vocab_size": int(vocab_size)}


def batch_for_step(plan: Dict[str, Any], step: int):
    """The token ids of step `step`: [batch, seq_len] int32, the same for
    the same seed and step wherever it is made."""
    import numpy as np

    rng = np.random.default_rng([plan["seed"], step])
    return rng.integers(0, plan["vocab_size"],
                        (plan["batch"], plan["seq_len"]), dtype=np.int32)
