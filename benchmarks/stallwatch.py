"""Notices when the process it runs in did not run.

A thread wakes every TICK_S and compares the clock with where it should
be.  A gap of MIN_GAP_S or more is kept with the CPU time the whole
process used inside it (`cpu_s`): about the gap, one of its threads ran
and did not let go of the interpreter (GIL); about 0, nothing of it ran.
The harness's process, each replica's and the Train worker's run one, and
`at` is on `time.time`: a gap that two processes show at the same `at`
with no CPU used is not theirs but the machine's.

PR 24 saw about one serving run in twelve stall for seconds, and with
this found the cause in one: the harness's process and the replica's
stopped within 0.2 ms of each other for the same 13.5 s and used 0.02 s
and 0.11 s of CPU in it.  Every run also shows such a stop of 3 to 5 s
in its set-up, in every process on the machine, when the worker opens the
chip (PERF.md sections 6 and 7).  `/proc/stat` and `schedstat` say
nothing on that machine (they do not advance), so they are not read.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

TICK_S = 0.05
MIN_GAP_S = 0.25


class StallWatch:

    def __init__(self):
        self.gaps: List[Dict[str, Any]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-stallwatch")
        self._thread.start()

    def _run(self) -> None:
        prev, cpu = time.monotonic(), time.process_time()
        while not self._stop.wait(TICK_S):
            now, cpu_now = time.monotonic(), time.process_time()
            gap = now - prev - TICK_S
            if gap >= MIN_GAP_S:
                self.gaps.append({"at": time.time() - gap, "gap_s": gap,
                                  "cpu_s": cpu_now - cpu})
            prev, cpu = now, cpu_now

    def stop(self) -> List[Dict[str, Any]]:
        self._stop.set()
        self._thread.join(10)
        return self.gaps
