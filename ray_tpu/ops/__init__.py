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
from typing import Any, Dict

import jax

from ray_tpu.ops.ring_attention import make_ring_attention, ring_attention

__all__ = ["ring_attention", "make_ring_attention", "kernel_mode",
           "device_report", "count_compile_cache_events"]

# persistent-compile-cache hits and misses of this process, counted from
# the first call of `count_compile_cache_events` on
_cache_events: Dict[str, int] = {}


def count_compile_cache_events() -> None:
    """Start counting this process's compile-cache hits and misses (once;
    touches no backend).  `device_report` calls it, so whoever wants the
    compiles of a start-up counted calls it before them: the serving
    engine does at construction, a train loop at its top."""
    if _cache_events:
        return
    _cache_events.update({"/jax/compilation_cache/cache_hits": 0,
                          "/jax/compilation_cache/cache_misses": 0})

    def on_event(event: str, **_kw) -> None:
        if event in _cache_events:
            _cache_events[event] += 1

    jax.monitoring.register_event_listener(on_event)


def kernel_mode() -> str:
    """"compiled" on a TPU backend, "interpret" on any other."""
    return "compiled" if jax.devices()[0].platform == "tpu" else "interpret"


def device_report() -> Dict[str, Any]:
    """What this process's jax runs on, as jax reports it: the device,
    the Pallas kernel mode, device memory in use and at peak, the chips
    its lease made visible, where the compile cache lives and how often
    it has hit since `count_compile_cache_events` was first called."""
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
                _cache_events["/jax/compilation_cache/cache_misses"]}
