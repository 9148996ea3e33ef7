"""The replica target of the hybrid expert cell: `LagunaReplica` (the
engine's `stats()` read just inside the two ends of the traced span) with
this family's plain reference and, as `OlmoReplica`, the recurrence's
carry read from the state pool.  It changes nothing of how a request is
served.
"""

from __future__ import annotations

from typing import Any, List

from benchmarks.replica_laguna import LagunaReplica


class Qwen3NextReplica(LagunaReplica):

    def bench_reference(self, prompts: List[List[int]],
                        answers: List[List[int]], picks=None,
                        reading=None) -> List[Any]:
        """`reference_qwen3next.teacher_forced` on this engine's weights;
        `reading`: one of that module's other readings."""
        from benchmarks import reference_qwen3next

        return reference_qwen3next.teacher_forced(
            self._engine._params, prompts, answers, self._sizes,
            picks=picks, reading=reading)

    def bench_carry(self, request, answer=None, reading=None):
        """The recurrence's CARRY itself (`kinds/serve_qwen3next.py`):
        each linear layer's state behind `request`'s prompt and its
        answer, against the reference's token-by-token state.  Without
        `reading` the states are the ENGINE's: the request is served
        here, alone on an idle engine, and its slot's rows are read from
        the pool once it has ended.  With `reading` they are that
        reading's of the reference, behind `answer`."""
        import numpy as np

        from benchmarks import reference_qwen3next as ref

        engine, params = self._engine, self._engine._params
        prompt = list(request["tokens"])
        if reading is not None:
            got = ref.carried_states(params, prompt, answer, self._sizes,
                                     reading=reading)
        else:
            seq, slot, answer = engine.submit(dict(request)), None, []
            try:
                for item in engine.iter_tokens(seq, 0):
                    slot = seq.cache.get("state", slot)
                    answer.extend(item["tokens"])
            finally:
                engine.release(seq)
            assert slot, "the sequence ended before its slot was seen"
            with engine._lock:
                rows = [np.asarray(pool[slot])
                        for pool in engine._pools["ssm"] if pool is not None]
            heads = int(self._sizes["linear_num_value_heads"])
            dv = int(self._sizes["linear_value_head_dim"])
            # the pool keeps the value heads in pairs side by side:
            # (heads / 2, dk, 2 dv) -> (heads, dk, dv)
            got = [np.moveaxis(r.reshape(r.shape[0], r.shape[1], -1, dv),
                               2, 1).reshape(heads, r.shape[1], dv)
                   for r in rows]
        want = ref.carried_states(params, prompt, answer, self._sizes)
        return {"tokens": answer, **ref.carry_distance(got, want)}
