"""The replica target of the SDAR serving cell: `LagunaReplica` (the
engine's `stats()` read inside the traced span) with this model's plain
reference, a canary asked WITH its record — each block pass's block as
read and as left (`record_passes`), which is what the reference is
teacher-forced with — and the routers BALANCED before the engine warms
up.  It changes nothing of how a request is served.

**Why the routers are balanced.**  A deployment's router is trained
with a balance loss: every expert sees its share of the tokens.  A
router drawn from a seed is not: the rows of a pass share a large common
part (with untrained weights attention adds much the same vector to
every row of a lane, and half the positions of a block pass hold one
token, the mask), so every row's router logits carry the same offset an
expert, some experts are chosen by nobody, and HOW MANY is the draw's:
a block pass touched 74 to 83 % of the 768 experts by the seed, the
experts' stream is two thirds of the pass, and every latency of the run
followed it (`tpot_p50_ms` 17.4 to 19.1 over nine seeds, PERF.md
section 6, PR 46): the spread between seeds was the draw's, not the
program's.  So, as `replica_pangu.py` places its experts by load, this
replica takes the common part out, layer by layer: it runs a sample
shaped like the traffic (sequences of random ids that end in a block
with one to four masks) through the engine's own model, takes the mean
input of the layer's router over the sample — the prompts' rows and the
open blocks' rows weighing alike — and removes from every column of
`moe_router` its component along that mean, so that no expert is ahead
of another on the sample's average row.  Nothing else of the weights
changes; the reference reads the same router.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

from benchmarks.replica_laguna import LagunaReplica

SAMPLE = (64, 64)     # sequences x positions routed to find the means
PASS_LANES = 27       # open blocks a pass of the cell holds: what is reported


def centred(router, mean):
    """`router` [D, E] (numpy, float32) less each column's component
    along `mean` [D]: the mean row then reads 0 for every expert, and a
    row's part across the mean reads what it read."""
    import numpy as np

    return router - np.outer(mean, mean @ router) / float(mean @ mean)


class SdarReplica(LagunaReplica):

    def __init__(self, warm: bool = True, sizes: Dict[str, Any] = None,
                 **engine_kwargs):
        super().__init__(warm=False, sizes=sizes, **engine_kwargs)
        t0 = time.monotonic()
        self._balance = self._balance_routers(int(engine_kwargs["seed"]))
        if warm:
            self._engine.warm_up()
        self._built_s += time.monotonic() - t0

    def _balance_routers(self, seed: int) -> List[Dict[str, Any]]:
        """The module's text; returns, a layer, the share of the experts
        that `PASS_LANES` of the sample's open blocks touched before and
        after."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.laguna import ExpertLayer

        eng = self._engine
        cfg, model = eng.cfg, eng._model
        block, mask = cfg.block_length, cfg.mask_token_id
        rng = np.random.default_rng(seed)
        n, s = SAMPLE
        tokens = rng.integers(1, min(mask, cfg.vocab_size), (n, s)).astype(
            np.int32)
        ends = block * rng.integers(2, s // block + 1, n)
        rows = np.zeros((n, s), bool)      # the rows a sequence holds
        opened = np.zeros((n, s), bool)    # those of its open block
        for i, end in enumerate(ends):
            tokens[i, end - 1 - i % block:end] = mask
            rows[i, :end] = True
            opened[i, end - block:end] = True

        @jax.jit
        def routed(params, tokens):
            """Every expert layer's input and the experts it chose (the
            tokens an argument: one program for every seed)."""
            _logits, state = model.apply(
                {"params": params}, tokens, mutable=["intermediates"],
                capture_intermediates=lambda m, _name: isinstance(
                    m, ExpertLayer) or m.name == "mlp_norm")
            return {layer: (found["mlp_norm"]["__call__"][0],
                            found["moe"]["__call__"][0][1]["ids"])
                    for layer, found in state["intermediates"].items()
                    if layer.startswith("layer_")}

        some = opened & (np.arange(n) < PASS_LANES)[:, None]

        def touched(ids) -> float:
            ids = np.asarray(ids).reshape(n, s, -1)
            return len(np.unique(ids[some])) / cfg.num_experts

        said = []
        for i in range(cfg.num_hidden_layers):
            # later layers read what this layer's experts add: one
            # forward a layer, each with the layers before it balanced
            name = f"layer_{i}"
            h, ids = routed(eng._params, tokens)[name]
            h = np.asarray(h, np.float32)
            mean = 0.5 * (h[rows].mean(axis=0) + h[opened].mean(axis=0))
            layer = eng._params[name]
            router = layer["moe"]["moe_router"]
            w = centred(np.asarray(router, np.float32), mean)
            eng._params = {**eng._params, name: {**layer, "moe": {
                **layer["moe"],
                "moe_router": jnp.asarray(w, router.dtype)}}}
            after = routed(eng._params, tokens)[name][1]
            said.append({"layer": i, "touched_as_drawn": touched(ids),
                         "touched_balanced": touched(after)})
        return said

    def bench_balance(self) -> List[Dict[str, Any]]:
        return list(self._balance)

    def generate_recorded(self, request) -> Dict[str, Any]:
        """`generate` through the engine's normal admission, streamed to
        its end here: {"tokens", "passes"}."""
        engine = self._engine
        seq = engine.submit({**request, "record_passes": True})
        tokens: List[int] = []
        try:
            for item in engine.iter_tokens(seq):
                tokens.extend(item["tokens"])
        finally:
            engine.release(seq)
        return {"tokens": tokens, "passes": [list(p) for p in seq.blk.passes]}

    def bench_reference(self, prompts: List[List[int]], trajectories,
                        matrices=None, mutant=None) -> List[Any]:
        """`reference_sdar.teacher_forced` on this engine's weights."""
        from benchmarks import reference_sdar

        return reference_sdar.teacher_forced(
            self._engine._params, prompts, trajectories, self._sizes,
            matrices=matrices, mutant=mutant)

    def bench_mutants(self) -> List[str]:
        """`reference_sdar.MUTANTS`, for a parent process that must not
        import the module (it imports jax)."""
        from benchmarks import reference_sdar

        return list(reference_sdar.MUTANTS)

    def bench_mutant(self, prompts: List[List[int]], max_new: int,
                     matrices=None, mutant=None) -> List[Any]:
        """What the reference's own loop generates with a mechanism done
        wrong or its matrices rounded lower (`reference_sdar.MUTANTS`),
        on this engine's weights: the records a comparison has to
        refuse."""
        from benchmarks import reference_sdar

        return [reference_sdar.generate(
            self._engine._params, p, max_new, self._sizes,
            matrices=matrices, mutant=mutant) for p in prompts]
