"""The replica target of the Laguna serving cells: `BenchReplica` with
this family's plain reference, and the engine's `stats()` read INSIDE
the traced span — just after the profiler has started and just before it
stops — so that the counters a roofline share divides by the trace's
device seconds count no work the trace did not see (PERF.md section 7,
"Still unseen": the polls' counters cover the window, the trace 4 s of
it).  It changes nothing of how a request is served.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmarks.replica import BenchReplica


class LagunaReplica(BenchReplica):

    def __init__(self, warm: bool = True, sizes: Dict[str, Any] = None,
                 **engine_kwargs):
        super().__init__(warm=warm, **engine_kwargs)
        self._sizes = sizes          # the configuration file's keys
        self._span_stats: List[Dict[str, Any]] = []

    def bench_reference(self, prompts: List[List[int]],
                        answers: List[List[int]], picks=None,
                        matrices=None) -> List[Any]:
        """`reference_laguna.teacher_forced` on this engine's weights:
        per prompt the reference's largest logits, its logits of the
        engine's tokens (or of `picks`), and each position's router
        margin; `matrices`: that module's second reading."""
        from benchmarks import reference_laguna

        return reference_laguna.teacher_forced(
            self._engine._params, prompts, answers, self._sizes,
            picks=picks, matrices=matrices)

    def profile_start(self, trace_dir: str) -> None:
        super().profile_start(trace_dir)
        self._span_stats = [self._engine.stats()]

    def profile_stop(self) -> None:
        self._span_stats.append(self._engine.stats())
        super().profile_stop()

    def profile_reduce(self, **kwargs) -> Dict[str, Any]:
        out = super().profile_reduce(**kwargs)
        if len(self._span_stats) == 2:
            out["span_stats"] = list(self._span_stats)
        return out
