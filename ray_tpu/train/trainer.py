"""JaxTrainer: SPMD training over a gang of TPU workers.

Equivalent of the reference's DataParallelTrainer + BackendExecutor
(reference: python/ray/train/data_parallel_trainer.py:22 training_loop
:420; _internal/backend_executor.py:65 — placement :197, rank mapping
:347, start_training :427, get_next_results :541), with torch process
groups replaced by jax.distributed + GSPMD meshes:

  - ScalingConfig declares workers and per-worker resources (TPU chips)
  - the parallelism layout travels as a MeshSpec in train_loop_config;
    inside the loop, `make_mesh(spec)` builds the mesh over the global
    device view (all hosts' chips after jax.distributed.initialize)
  - worker failure fails the run (Train is not elastic in the reference
    either — SURVEY §5.3; restart-from-checkpoint is the recovery path)
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

from ray_tpu.train.worker_group import WorkerGroup


class TrainingFailedError(Exception):
    pass


@dataclass
class ScalingConfig:
    """Reference: python/ray/air/config.py ScalingConfig."""

    num_workers: int = 1
    use_tpu: bool = False
    resources_per_worker: Optional[Dict[str, float]] = None

    def worker_resources(self) -> Dict[str, float]:
        if self.resources_per_worker is not None:
            return dict(self.resources_per_worker)
        return {"TPU": _chips_per_host()} if self.use_tpu else {}


def _chips_per_host() -> float:
    """One Train worker drives one host's chips: what the cluster's TPU
    nodes report (the smallest, so every worker schedules), or 4 — a
    v5e/v4 host — when no TPU node has joined yet and the demand is the
    autoscaler's to meet."""
    import ray_tpu

    counts = [n["resources"]["total"].get("TPU", 0) for n in ray_tpu.nodes()]
    return min((c for c in counts if c > 0), default=4)


@dataclass
class RunConfig:
    name: str = "train_run"
    storage_path: str = "/tmp/ray_tpu_results"
    failure_max_retries: int = 0


@dataclass
class Result:
    metrics: Dict[str, Any] = field(default_factory=dict)
    metrics_history: List[Dict[str, Any]] = field(default_factory=list)
    checkpoint: Optional[str] = None
    error: Optional[BaseException] = None
    per_worker_final: List[Any] = field(default_factory=list)


class JaxTrainer:
    def __init__(self, train_loop_per_worker: Callable,
                 *, scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[str] = None,
                 datasets: Optional[Dict[str, Any]] = None):
        self.train_loop = train_loop_per_worker
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.config = train_loop_config
        self.resume_from_checkpoint = resume_from_checkpoint
        self.datasets = datasets or {}

    def fit(self) -> Result:
        """Run to completion, rebuilding the worker gang and resuming from
        the last reported checkpoint on failure, up to
        RunConfig.failure_max_retries times (reference:
        backend_executor.get_with_failure_handling :629 +
        tune_controller._schedule_trial_restore :1792 — Train is gang-
        restart, not elastic)."""
        resume = self.resume_from_checkpoint
        history: List[Dict[str, Any]] = []
        failures = 0
        while True:
            group = WorkerGroup(self.scaling.num_workers,
                                self.scaling.worker_resources())
            try:
                return self._fit(group, resume, history)
            except TrainingFailedError as e:
                ckpt = getattr(e, "last_checkpoint", None)
                if ckpt:
                    resume = ckpt
                if failures >= self.run_config.failure_max_retries:
                    raise
                failures += 1
            finally:
                group.shutdown()

    def _fit(self, group: WorkerGroup, resume: Optional[str] = None,
             history: Optional[List[Dict[str, Any]]] = None) -> Result:
        import os

        n = group.num_workers
        trial_dir = os.path.join(self.run_config.storage_path,
                                 f"{self.run_config.name}-{int(time.time())}")
        os.makedirs(trial_dir, exist_ok=True)
        import ray_tpu

        # multi-process rendezvous (reference: backend_executor start —
        # rank 0 address/port shared with the gang before the loop starts)
        if n > 1:
            try:
                info0 = group.execute_single(0, "node_info")
                port = group.execute_single(0, "free_port")
                coordinator = f"{info0['ip']}:{port}"
                self._init_distributed(group, coordinator, n)
            except ray_tpu.RayError as e:
                err = TrainingFailedError(
                    f"worker gang failed during rendezvous: {e}")
                err.last_checkpoint = resume
                raise err from e
        fn_blob = cloudpickle.dumps(self.train_loop)
        # dataset ingest: each worker gets its round-robin block shard
        # (reference: _internal/data_config.py streaming_split)
        shard_map: Dict[int, Dict[str, Any]] = {r: {} for r in range(n)}
        for name, ds in self.datasets.items():
            for rank, shard in enumerate(ds.split(n)):
                shard_map[rank][name] = shard
        import ray_tpu

        refs = []
        for rank, w in enumerate(group.workers):
            refs.append(w.run_async.remote(
                fn_blob, self.config, checkpoint=resume,
                experiment_name=self.run_config.name, trial_dir=trial_dir,
                datasets=shard_map[rank] or None))
        try:
            ray_tpu.get(refs, timeout=120.0)
        except ray_tpu.RayError as e:
            err = TrainingFailedError(f"worker gang failed to launch: {e}")
            err.last_checkpoint = resume
            raise err from e
        return self._poll_until_done(group, trial_dir, history)

    def _init_distributed(self, group: WorkerGroup, coordinator: str, n: int):
        import ray_tpu

        refs = [w.init_jax_distributed.remote(coordinator, n, rank)
                for rank, w in enumerate(group.workers)]
        ray_tpu.get(refs, timeout=300.0)

    def _poll_until_done(self, group: WorkerGroup, trial_dir: str,
                         history: Optional[List[Dict[str, Any]]] = None) -> Result:
        import ray_tpu

        history = history if history is not None else []
        last_checkpoint: Optional[str] = None
        done = [False] * group.num_workers
        finals: List[Any] = [None] * group.num_workers

        def _fail(msg: str, cause: BaseException):
            err = TrainingFailedError(msg)
            err.last_checkpoint = last_checkpoint  # resume point for fit()
            raise err from cause

        while not all(done):
            time.sleep(0.05)
            try:
                polls = group.execute("poll", timeout=120.0)
            except (ray_tpu.ActorDiedError, ray_tpu.RayError) as e:
                _fail(f"a training worker died mid-run: {e}", e)
            for rank, p in enumerate(polls):
                for rep in p["reports"]:
                    if rank == 0 and "_error" not in rep["metrics"]:
                        history.append(rep["metrics"])
                    if rep.get("checkpoint"):
                        last_checkpoint = rep["checkpoint"]
                if p["done"] and not done[rank]:
                    done[rank] = True
                    if p["error"] is not None:
                        err = cloudpickle.loads(p["error"])
                        _fail(f"train loop failed on rank {rank}: {err}", err)
                    finals[rank] = p["final"]
        return Result(metrics=history[-1] if history else {},
                      metrics_history=history,
                      checkpoint=last_checkpoint,
                      per_worker_final=finals)
