"""The replica target of the sparse-attention cell: `PanguReplica` (the
engine's `stats()` read just inside the two ends of the traced span, and
this chip's 16 experts PLACED by load before the engine warms up:
`replica_pangu.py` has why) with this family's plain reference.  This
family's router has a selection bias an expert, which goes with the
expert's column wherever the placement puts it.  It changes nothing of
how a request is served.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmarks.replica_pangu import SAMPLE, PanguReplica


class GlmReplica(PanguReplica):

    def _place_experts(self, seed: int) -> List[Dict[str, Any]]:
        """`PanguReplica._place_experts` with the bias carried along:
        each expert layer's router columns AND bias entries are permuted
        to the order that gives this chip's 16 experts a sixteenth of
        the sample's assignments."""
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
            moe = {**layer["moe"],
                   "moe_router": layer["moe"]["moe_router"][:, order],
                   "moe_router_bias": layer["moe"]["moe_router_bias"][order]}
            eng._params = {**eng._params, name: {**layer, "moe": moe}}
            said.append({"layer": i,
                         "share_as_drawn": float(loads[lo:hi].sum()
                                                 / loads.sum()),
                         "share_placed": float(loads[order][lo:hi].sum()
                                               / loads.sum())})
        return said

    def bench_reference(self, prompts: List[List[int]],
                        answers: List[List[int]], picks=None,
                        matrices=None, variant=None) -> List[Any]:
        """`reference_glm.teacher_forced` on this engine's weights."""
        from benchmarks import reference_glm

        return reference_glm.teacher_forced(
            self._engine._params, prompts, answers, self._sizes,
            picks=picks, matrices=matrices, variant=variant)
