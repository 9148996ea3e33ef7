"""The replica target of the hybrid state-space cell: `LagunaReplica`
(the engine's `stats()` read just inside the two ends of the traced
span) with this family's plain reference.  It changes nothing of how a
request is served.
"""

from __future__ import annotations

from typing import Any, List

from benchmarks.replica_laguna import LagunaReplica


class GraniteReplica(LagunaReplica):

    def bench_reference(self, prompts: List[List[int]],
                        answers: List[List[int]], picks=None,
                        reading=None) -> List[Any]:
        """`reference_granite.teacher_forced` on this engine's weights;
        `reading`: one of that module's other readings."""
        from benchmarks import reference_granite

        return reference_granite.teacher_forced(
            self._engine._params, prompts, answers, self._sizes,
            picks=picks, reading=reading)
