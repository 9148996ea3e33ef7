"""Accelerator detection: TPU chips/slices as first-class resources.

Equivalent of the reference's accelerator plugin layer
(reference: python/ray/_private/accelerators/accelerator.py:5 ABC;
tpu.py:75 TPUAcceleratorManager — /dev/accel* detection :110,
TPU_VISIBLE_CHIPS :30, GCE/GKE metadata :52, pod-slice custom resources
TPU-{type}-head and slice-name resources :335-398).

Detection is cheap (no jax import): device files + env vars + GCE
metadata when present.  A node on a pod slice additionally advertises
  - "TPU-<accel_type>-head": 1   on worker 0 of the slice (gang anchor)
  - "TPU-<slice_name>": 4        so a placement group can target a slice
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional

TPU_RESOURCE = "TPU"

_DEV_ROOT = "/dev"  # tests point this at a fake tree


def num_tpu_chips() -> int:
    env = os.environ.get("TPU_VISIBLE_CHIPS")
    if env is not None:
        return 0 if env in ("", "none") else len(env.split(","))
    # PCI accel device files (reference: tpu.py:110
    # _glob_tpu_acclerator_devices): one numbered node per chip.
    # /dev/vfio also holds the `vfio` control node, which is no chip.
    for pattern, prefix in (("accel*", "accel"), ("vfio/*", "")):
        names = [os.path.basename(p)[len(prefix):]
                 for p in glob.glob(os.path.join(_DEV_ROOT, pattern))]
        chips = sum(1 for n in names if n.isdigit())
        if chips:
            return chips
    return 0


# chips in one process -> the per-process topology libtpu has to be told
# when processes share a host (reference: tpu.py
# set_current_process_visible_accelerator_ids sets the same pair under
# their older *_HOST_BOUNDS names)
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def chip_env(tpu_chips: List[int], host_chips: int) -> Dict[str, str]:
    """Environment for a worker about to run a lease that holds
    `tpu_chips` on a host with `host_chips`.  Applied before the worker's
    first `import jax`: a TPU lease always gets a fresh worker
    (node_agent._pop_worker), so the backend comes up on exactly these
    chips.  A lease with no chips on a host that has some must come up on
    the CPU backend, or its jax would open every chip.

    A process given fewer chips than the host has gets the per-process
    bounds too.  Seen on a four-chip v5e host with libtpu 0.0.34:
    TPU_VISIBLE_CHIPS alone brings ONE such process up on its chip, but
    the second one beside it aborts on libtpu's single-process lockfile;
    with the bounds four ran side by side."""
    if not tpu_chips:
        return {"JAX_PLATFORMS": "cpu"} if host_chips > 0 else {}
    env = {"TPU_VISIBLE_CHIPS": ",".join(map(str, tpu_chips))}
    bounds = _PROCESS_BOUNDS.get(len(tpu_chips))
    if bounds and len(tpu_chips) < host_chips:
        env.update(TPU_CHIPS_PER_PROCESS_BOUNDS=bounds,
                   TPU_PROCESS_BOUNDS="1,1,1")
    return env


_metadata_cache: Dict[str, Optional[str]] = {}


def _gce_metadata_http(key: str) -> Optional[str]:
    """GCE/GKE metadata-server lookup (reference: tpu.py:52
    _get_tpu_metadata — GKE TPU pods expose accelerator-type and
    agent-worker-number through the instance metadata server).  Cached;
    fails fast off-GCP."""
    if key in _metadata_cache:
        return _metadata_cache[key]
    value: Optional[str] = None
    try:
        import urllib.request

        req = urllib.request.Request(
            "http://metadata.google.internal/computeMetadata/v1/"
            f"instance/attributes/{key}",
            headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=0.5) as r:
            value = r.read().decode().strip()
    except Exception:
        value = None
    _metadata_cache[key] = value
    return value


def tpu_metadata(key: str) -> Optional[str]:
    """TPU slice metadata: env vars first (GKE injects them; tests set
    them), then the GCE metadata server, else None."""
    env_map = {
        "accelerator-type": "TPU_ACCELERATOR_TYPE",
        "agent-worker-number": "TPU_WORKER_ID",
        "instance-id": "TPU_NAME",
    }
    env = env_map.get(key)
    if env and os.environ.get(env) is not None:
        return os.environ.get(env)
    if os.environ.get("RT_DISABLE_METADATA_SERVER") or not _on_gce():
        return None  # off-GCP: keep the zero-egress guarantee
    return _gce_metadata_http(key)


def _on_gce() -> bool:
    """Detect GCE/GKE via DMI — no network, so off-GCP hosts never pay
    a DNS stall for metadata.google.internal."""
    try:
        with open("/sys/class/dmi/id/product_name") as f:
            return "Google" in f.read()
    except OSError:
        return False


def num_gpus() -> int:
    """NVIDIA GPU count via CUDA_VISIBLE_DEVICES / device files
    (reference: accelerators/nvidia_gpu.py — TPU is the primary target
    here, but mixed clusters schedule GPUs as ordinary resources)."""
    env = os.environ.get("CUDA_VISIBLE_DEVICES")
    if env is not None:
        ids = [d for d in env.split(",") if d.strip() not in ("", "-1")]
        return 0 if env.strip() in ("", "none", "NoDevFiles", "-1") \
            else len(ids)
    return len([p for p in glob.glob("/dev/nvidia[0-9]*")
                if p[len("/dev/nvidia"):].isdigit()])


def detect_accelerators() -> Dict[str, float]:
    out: Dict[str, float] = {}
    gpus = num_gpus()
    if gpus > 0:
        out["GPU"] = float(gpus)
    chips = num_tpu_chips()
    if chips <= 0:
        return out
    out[TPU_RESOURCE] = float(chips)
    accel_type = tpu_metadata("accelerator-type")  # e.g. "v5e-256"
    worker_id = tpu_metadata("agent-worker-number")
    slice_name = tpu_metadata("instance-id")
    if accel_type:
        if worker_id == "0":
            out[f"TPU-{accel_type}-head"] = 1.0
    if slice_name:
        out[f"TPU-{slice_name}"] = float(chips)
    return out
