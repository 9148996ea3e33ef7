"""From a profiler trace (`.xplane.pb`) to what the benchmark reports of
the device: busy and idle time, time per operation name, and the longest
idle gaps with what the host was doing in them.

Read with `jax.profiler.ProfileData` (nothing but jax).  Which events are
device operations:

  - on a TPU, the events of the line "XLA Ops" of every plane named
    "/device:TPU:<n>" (one plane a chip);
  - in a trace from the CPU backend (the recorded trace in the tests),
    the events that carry an `hlo_op` stat on the host plane's threads,
    taken together as one device.

On a TPU an operation's name in the trace is its whole HLO text; `label`
shortens it and gives the same operation of every layer one name.

Busy time of a device is the UNION of its operations' intervals (ops can
overlap); the window is from the first operation's start to the last
one's end over all devices; `busy_s` is the mean over the devices.  A gap
is a stretch of the window with no operation on a device, reported from
`min_gap_s` up and attributed to the innermost host span (the shortest
one) that covers at least half of it; where none does, to
`unattributed`.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


_OP = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)* = \(?([a-z0-9]+\[[\d,]*\])?")
_WEIGHT = re.compile(r"%((?:[A-Za-z0-9]+_)*?_[\w]*?__)(?:\.\d+)?[,)]")


def label(hlo: str) -> str:
    """A short, stable name for a device operation whose trace name is
    its whole HLO text: the instruction's name without its number, the
    shape of its (first) result, the weights among its operands with the
    layer's number starred, and `tpu_custom_call` where it is a Pallas
    kernel.  The same operation of every layer then has one label, e.g.
    `fusion bf16[16,4096] [layer_*.mlp.w2.kernel, layer_*.mlp.w3.kernel]`.
    A name that is not HLO text is returned as it is."""
    m = _OP.match(hlo)
    if m is None:
        return hlo
    parts = [m.group(1)]
    if 'custom_call_target="tpu_custom_call"' in hlo:
        parts.append("tpu_custom_call")
    if m.group(2):
        parts.append(m.group(2))
    weights = []
    for w in _WEIGHT.findall(hlo):
        w = re.sub(r"^(params|p)__", "", w.strip("_"))
        w = re.sub(r"layer_\d+", "layer_*", w.replace("____", "."))
        w = w.replace("__", ".")
        if w not in weights:
            weights.append(w)
    if weights:
        parts.append("[" + ", ".join(weights[:3]) + "]")
    return " ".join(parts)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _stat(event, key: str) -> Optional[str]:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return None


def load(path: str) -> Dict[str, Any]:
    """{"devices": {plane: [(start_s, end_s, name)]}, "host": [(start_s,
    end_s, name)]} of one trace file; a device operation's name is its
    `label`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[Tuple[float, float, str]] = []
    cpu_ops: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                rows = devices.setdefault(plane.name, [])
                for e in line.events:
                    s = e.start_ns * 1e-9
                    rows.append((s, s + e.duration_ns * 1e-9,
                                 label(e.name)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    row = (s, s + e.duration_ns * 1e-9, e.name)
                    if _stat(e, "hlo_op") is not None:
                        cpu_ops.append(row)
                    elif e.duration_ns > 0:
                        host.append(row)
    if not devices and cpu_ops:
        devices["/host:CPU (hlo ops)"] = cpu_ops
    return {"devices": devices, "host": host}


def _union(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _attribute(gap: Tuple[float, float], host, prefer: str,
               unattributed: str) -> str:
    gs, ge = gap
    best, best_key = unattributed, None
    for s, e, name in host:
        cover = min(e, ge) - max(s, gs)
        if cover < 0.5 * (ge - gs):
            continue
        # one of the benchmark's own spans before any other; then the
        # innermost, which is the shortest
        key = (0 if name.startswith(prefer) else 1, e - s)
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def reduce(path: str, **kwargs) -> Dict[str, Any]:
    """`reduce_events` of the trace file at `path`."""
    return reduce_events(**load(path), **kwargs)


def reduce_events(devices: Dict[str, list], host: list, *,
                  min_gap_s: float = 1e-3, top: int = 10,
                  prefer: str = "bench:",
                  unattributed: str = "host, unattributed"
                  ) -> Dict[str, Any]:
    """The reduction described at the top of this file, of `load`'s
    rows.  None's in place of numbers when there is no device
    operation."""
    ops = [row for rows in devices.values() for row in rows]
    if not ops:
        return {"devices": 0, "busy_s": None, "window_s": None,
                "device_ops": [], "idle_gaps": [], "op_seconds": {}}
    w0 = min(r[0] for r in ops)
    w1 = max(r[1] for r in ops)
    busy, gaps = [], {}
    for rows in devices.values():
        merged = _union([(r[0], r[1]) for r in rows])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge - gs >= min_gap_s:
                label = _attribute((gs, ge), host, prefer, unattributed)
                gaps[label] = gaps.get(label, 0.0) + (ge - gs)
    n = len(devices)
    op_seconds: Dict[str, float] = {}
    for s, e, name in ops:
        op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / n
    ranked = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    return {
        "devices": n, "busy_s": sum(busy) / n, "window_s": w1 - w0,
        # seconds a device, mean over the devices
        "device_ops": [[k, v] for k, v in ranked[:top]],
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "op_seconds": op_seconds,
    }


def seconds_matching(reduced: Dict[str, Any], pattern: str) -> float:
    """Device seconds (mean over devices) of the operations whose label
    matches `pattern`."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["op_seconds"].items() if rx.search(k))
