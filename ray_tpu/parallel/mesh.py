"""Device mesh construction and sharding helpers.

The mesh axis vocabulary follows the scaling-book convention:
  - "dp":   pure data parallelism (params replicated, batch sharded)
  - "fsdp": fully-sharded data parallelism (params + batch sharded)
  - "tp":   tensor parallelism (heads / mlp-hidden sharded)
  - "sp":   sequence/context parallelism (sequence dim sharded; ring
            attention carries the KV rotation over ICI)
  - "pp":   pipeline stages

The reference has no equivalent — torch DDP/FSDP wrap modules
(reference: python/ray/train/torch/train_loop_utils.py:158 prepare_model);
here a `MeshSpec` lowers to a `jax.sharding.Mesh` + `PartitionSpec` rules
and XLA/GSPMD does the rest.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "tp")


@dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout; -1 on at most one axis means 'fill'."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        fills = [a for a, v in sizes.items() if v == -1]
        if len(fills) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if fills:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[fills[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return MeshSpec(**sizes)

    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def nontrivial_axes(self) -> List[str]:
        return [a for a in AXIS_ORDER if getattr(self, a) > 1]


def mesh_axes_for(n_devices: int, spec: Optional[MeshSpec] = None
                  ) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    spec = (spec or MeshSpec(dp=-1)).resolve(n_devices)
    sizes = spec.axis_sizes()
    return tuple(AXIS_ORDER), tuple(sizes[a] for a in AXIS_ORDER)


def make_mesh(spec: Optional[MeshSpec] = None, devices: Optional[Sequence] = None):
    """Build a Mesh over the given (default: all) devices.

    Axes are laid out in AXIS_ORDER so that the innermost axes (tp, sp)
    map to the most tightly ICI-coupled device neighbourhoods — XLA's
    device assignment for TPU slices keeps later mesh dims closer.
    """
    import jax
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    names, sizes = mesh_axes_for(len(devices), spec)
    dev_array = np.array(devices).reshape(sizes)
    return jax.sharding.Mesh(dev_array, names)


def batch_pspec():
    """PartitionSpec for an activation batch dim: sharded over dp+fsdp."""
    from jax.sharding import PartitionSpec as P

    return P(("dp", "fsdp"))


def shard_batch(mesh, batch):
    """NamedSharding a pytree of host arrays: dim 0 over (dp, fsdp),
    dim 1 (sequence) over sp when present."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(x):
        if getattr(x, "ndim", 0) >= 2 and mesh.shape.get("sp", 1) > 1:
            spec = P(("dp", "fsdp"), "sp")
        elif getattr(x, "ndim", 0) >= 1:
            spec = P(("dp", "fsdp"))
        else:
            spec = P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, batch)


def param_shardings(mesh, params, rules: Optional[Dict[str, Any]] = None):
    """NamedShardings for a parameter pytree (arrays or the shapes
    `jax.eval_shape` gives): the largest dim of each leaf is sharded over
    'fsdp', unless an explicit per-path rule (tp) names the leaf.

    This is the generic fallback; models ship precise PartitionSpec rules
    (see ray_tpu/models/llama.py param_pspecs) that this function accepts
    via `rules` keyed by joined path.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    fsdp = mesh.shape.get("fsdp", 1)

    def spec_for(path, x) -> "P":
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if rules:
            for pat, spec in rules.items():
                if pat in key:
                    return spec
        if fsdp > 1 and getattr(x, "ndim", 0) >= 1:
            dims = list(x.shape)
            best = max(range(len(dims)), key=lambda i: dims[i])
            if dims[best] % fsdp == 0:
                spec = [None] * len(dims)
                spec[best] = "fsdp"
                return P(*spec)
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda path, x: NamedSharding(mesh, spec_for(path, x)), params)


def shard_params(mesh, params, rules: Optional[Dict[str, Any]] = None):
    """Place an existing parameter pytree per `param_shardings`."""
    import jax

    return jax.device_put(params, param_shardings(mesh, params, rules))


def init_sharded(mesh, init_fn, rng, rules: Optional[Dict[str, Any]] = None):
    """Run `init_fn(rng) -> params` with every leaf created in its target
    sharding: no device ever holds the whole tree, so a model that fits
    only across the mesh can be initialised at all."""
    import jax

    shardings = param_shardings(mesh, jax.eval_shape(init_fn, rng), rules)
    return jax.jit(init_fn, out_shardings=shardings)(rng)
