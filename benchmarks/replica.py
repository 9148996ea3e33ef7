"""The replica target of the serving cells: the program's own
`_LLMCallable`, plus what only the process that holds the chip can do for
a benchmark — start and stop the profiler, reduce its trace, run the
plain reference on the engine's own weights, read the device's memory
peak, count compiles and notice when the process did not run.  It
changes nothing of how a request is served.

Bound into the same `Deployment(..., llm=True)` that
`serve.llm_deployment` builds (kinds/serve.py).  Importing this module
imports no jax: the parent of a run imports it to name the class.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List

from ray_tpu.serve.llm import _LLMCallable

from benchmarks.stallwatch import StallWatch

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchReplica(_LLMCallable):

    def __init__(self, warm: bool = True, **engine_kwargs):
        import jax

        self._compiles = 0

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == COMPILE_EVENT:
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        t0 = time.monotonic()
        super().__init__(warm=warm, **engine_kwargs)
        self._built_s = time.monotonic() - t0
        self._trace_dir = None
        self._stalls = StallWatch()

    # ------------------------------------------------------------ counters

    def bench_state(self) -> Dict[str, Any]:
        """The engine's `stats()` with this process's compile count, the
        device's memory reading and the host's clock."""
        import jax

        try:
            mem = jax.devices()[0].memory_stats() or {}
        except Exception:  # a backend without memory stats (the CPU's)
            mem = {}
        out = self._engine.stats()
        out.update(t=time.time(), backend_compiles=self._compiles,
                   built_s=self._built_s,
                   max_batch=self._engine.max_batch,
                   memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)))
        return out

    def bench_stalls(self) -> List[Dict[str, Any]]:
        """When this process did not run, so far (stallwatch.py)."""
        return list(self._stalls.gaps)

    def bench_cancel(self, request_ids: List[str]) -> int:
        """End the named sequences now (the window of an overloaded cell
        is over and its open streams are dropped)."""
        return sum(bool(self._engine.cancel(r)) for r in request_ids)

    # ----------------------------------------------------------- reference

    def bench_reference(self, prompts: List[List[int]],
                        answers: List[List[int]]) -> List[Any]:
        """The plain reference on this engine's weights, teacher-forced
        with the engine's own answers: `reference.teacher_forced`."""
        from benchmarks import reference

        cfg = self._engine.cfg
        return reference.teacher_forced(
            self._engine._params, prompts, answers, n_layers=cfg.n_layers,
            theta=cfg.rope_theta, eps=cfg.norm_eps)

    # -------------------------------------------------------------- tracing

    def profile_start(self, trace_dir: str) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host TraceMe spans, no Python frames
        opts.host_tracer_level = 2
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._trace_dir = trace_dir

    def profile_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def profile_reduce(self, **kwargs) -> Dict[str, Any]:
        """After the window: the stopped trace reduced in this process
        (reading it needs jax, which the parent never imports)."""
        from benchmarks import trace_reduce

        path = trace_reduce.find_xplane(self._trace_dir)
        if path is None:
            return {"devices": 0, "busy_s": None, "window_s": None}
        out = trace_reduce.reduce(path, **kwargs)
        out["trace_bytes"] = os.path.getsize(path)
        return out
