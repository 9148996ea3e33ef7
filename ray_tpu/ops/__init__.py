"""TPU compute kernels: ring attention, flash attention, paged attention.

The reference has no sequence/context parallelism anywhere (SURVEY §5.7
— verified absent), so this package is green-field: long-context support
is built as a first-class mesh axis ("sp") with KV rotation over ICI.

The Pallas kernels compile for the TPU and run in the Pallas interpreter
anywhere else (tier-1 runs on the CPU).  That choice is made in one
place, `kernel_mode`, and `device_report` says which way it went: a
worker that failed to find its chip must not serve from the interpreter
without a word (`chip_smoke.py` fails on anything but compiled-on-TPU).
"""

import os
import threading
from collections import deque
from typing import Any, Dict, Optional

import jax

from ray_tpu.ops.ring_attention import make_ring_attention, ring_attention

__all__ = ["ring_attention", "make_ring_attention", "kernel_mode",
           "device_report", "count_compile_cache_events", "compile_counts",
           "note_phase"]

# persistent-compile-cache hits and misses of this process, counted from
# the first call of `count_compile_cache_events` on
_cache_events: Dict[str, int] = {}

# backend compiles of this process, counted from the same call on: how
# many, their seconds, and the last few with what the compiling thread
# said it was doing (`note_phase`) — "which step recompiled"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = {"compiles_total": 0, "compile_secs_total": 0.0}
_recent_compiles: deque = deque(maxlen=8)
_doing = threading.local()


def note_phase(phase: Optional[str], step: Optional[int] = None) -> None:
    """Name what this thread does from here on, for the record of a
    compile it may set off (jax reports a compile on the thread that
    called the jitted function).  The serving engine's loop names its
    step and phase; a thread that named nothing reads None."""
    _doing.phase, _doing.step = phase, step


def count_compile_cache_events() -> None:
    """Start counting this process's compile-cache hits and misses and
    its backend compiles (once; touches no backend).  `device_report`
    calls it, so whoever wants the compiles of a start-up counted calls
    it before them: the serving engine does at construction, a train
    loop at its top."""
    if _cache_events:
        return
    _cache_events.update({"/jax/compilation_cache/cache_hits": 0,
                          "/jax/compilation_cache/cache_misses": 0})

    def on_event(event: str, **_kw) -> None:
        if event in _cache_events:
            _cache_events[event] += 1

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            _compiles["compiles_total"] += 1
            _compiles["compile_secs_total"] += secs
            _recent_compiles.append(
                {"secs": secs, "phase": getattr(_doing, "phase", None),
                 "step": getattr(_doing, "step", None)})

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def compile_counts() -> Dict[str, Any]:
    """`compiles_total` and `compile_secs_total` of this process since
    `count_compile_cache_events` was first called."""
    return dict(_compiles)


def kernel_mode() -> str:
    """"compiled" on a TPU backend, "interpret" on any other."""
    return "compiled" if jax.devices()[0].platform == "tpu" else "interpret"


def interpret_default(interpret: Optional[bool]) -> bool:
    """A kernel's `interpret` argument: as given, or by the backend."""
    return kernel_mode() == "interpret" if interpret is None else interpret


def device_report() -> Dict[str, Any]:
    """What this process's jax runs on, as jax reports it: the device,
    the Pallas kernel mode, device memory in use and at peak, the chips
    its lease made visible, where the compile cache lives, how often it
    has hit and what was compiled (the totals, and the last few compiles
    with the phase and step their thread had named) since
    `count_compile_cache_events` was first called."""
    count_compile_cache_events()
    dev = jax.devices()[0]
    try:
        mem = dev.memory_stats() or {}
    except Exception:  # backends without memory stats
        mem = {}
    return {"pid": os.getpid(),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "chips_per_process_bounds": os.environ.get(
                "TPU_CHIPS_PER_PROCESS_BOUNDS"),
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "device_ids": [d.id for d in jax.local_devices()],
            "kernel_mode": kernel_mode(),
            "bytes_in_use": mem.get("bytes_in_use"),
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "compile_cache_hits":
                _cache_events["/jax/compilation_cache/cache_hits"],
            "compile_cache_misses":
                _cache_events["/jax/compilation_cache/cache_misses"],
            **compile_counts(),
            "recent_compiles": list(_recent_compiles)}
