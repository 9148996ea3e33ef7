"""The replica target of the latent-attention cell: `LagunaReplica` (the
engine's `stats()` read just inside the two ends of the traced span)
with this family's plain reference, and this chip's experts PLACED by
load before the engine warms up.  It changes nothing of how a request
is served.

**Why the experts are placed.**  The configuration stands for one of 16
chips that divide each layer's 256 experts.  As drawn from a seed, the
16 experts that fall to share 0 take 0.43 to 0.55 of a token's 8
assignments by the seed (PERF.md section 6, PR 34: 766 k to 983 k held
assignments over one window's prefill passes), and every latency of the
run follows that draw (`prefill_pass_ms` 33.0 to 34.8 ms): the spread
between seeds was the draw's, not the program's.  A deployment places
its experts on its chips by load, and so does this replica, as
`kinds/train_mellum.py` does for the training cell and with its
`place_by_load`: layer by layer it routes a sample of the traffic's
token ids through the engine's own model, orders the experts so that
each chip's 16 carry a sixteenth of the assignments, and permutes the
router's columns to that order — which experts live here, decided the
way a deployment decides it.  The held experts' matrices are the seed's
first 16 either way; the reference reads the same permuted router.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

from benchmarks.replica_laguna import LagunaReplica

SAMPLE = (8, 512)     # sequences x tokens routed to measure the loads


class PanguReplica(LagunaReplica):

    def __init__(self, warm: bool = True, sizes: Dict[str, Any] = None,
                 **engine_kwargs):
        super().__init__(warm=False, sizes=sizes, **engine_kwargs)
        t0 = time.monotonic()
        self._placement = self._place_experts(int(engine_kwargs["seed"]))
        if warm:
            self._engine.warm_up()
        self._built_s += time.monotonic() - t0

    def _place_experts(self, seed: int) -> List[Dict[str, Any]]:
        """Permute each expert layer's router columns so that the
        experts held here carry their chip's share of the sample's
        assignments; returns, a layer, the share before and after."""
        import jax
        import numpy as np

        from benchmarks.kinds.train_mellum import place_by_load
        from ray_tpu.models.laguna import ExpertLayer

        eng = self._engine
        cfg, model = eng.cfg, eng._model
        lo, hi = cfg.experts_held
        groups = cfg.n_routed_experts // (hi - lo)
        tokens = np.random.default_rng(seed).integers(
            1, cfg.vocab_size, SAMPLE).astype(np.int32)

        @jax.jit
        def chosen(params, tokens):
            """The experts each expert layer chose for every token (the
            tokens an argument: one program for every seed, which the
            compile cache then holds)."""
            _logits, state = model.apply(
                {"params": params}, tokens, mutable=["intermediates"],
                capture_intermediates=lambda m, _name: isinstance(
                    m, ExpertLayer))
            return {layer: found["moe"]["__call__"][0][1]["ids"]
                    for layer, found in state["intermediates"].items()}

        said = []
        for i in range(cfg.first_k_dense_replace, cfg.num_hidden_layers):
            # later layers read what this layer's held experts add: one
            # forward a layer, each with the layers before it placed
            name = f"layer_{i}"
            ids = np.asarray(chosen(eng._params, tokens)[name]).reshape(-1)
            loads = np.bincount(ids, minlength=cfg.n_routed_experts)
            order = np.asarray(place_by_load(loads, groups))
            layer = eng._params[name]
            router = layer["moe"]["moe_router"][:, order]
            eng._params = {**eng._params, name: {
                **layer, "moe": {**layer["moe"], "moe_router": router}}}
            said.append({"layer": i,
                         "share_as_drawn": float(loads[lo:hi].sum()
                                                 / loads.sum()),
                         "share_placed": float(loads[order][lo:hi].sum()
                                               / loads.sum())})
        return said

    def bench_placement(self) -> List[Dict[str, Any]]:
        return list(self._placement)

    def bench_reference(self, prompts: List[List[int]],
                        answers: List[List[int]], picks=None,
                        matrices=None) -> List[Any]:
        """`reference_pangu.teacher_forced` on this engine's weights."""
        from benchmarks import reference_pangu

        return reference_pangu.teacher_forced(
            self._engine._params, prompts, answers, self._sizes,
            picks=picks, matrices=matrices)
