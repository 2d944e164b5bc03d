"""Trainers: DataParallelTrainer / JaxTrainer.

Reference analogues: `python/ray/train/base_trainer.py:570` (``fit``),
`python/ray/train/data_parallel_trainer.py:58,432` (worker fan-out +
``training_loop``), `python/ray/train/trainer.py:41` (``TrainingIterator``
draining result rounds, restarting on failure).

The reference routes every Trainer through Tune; here ``fit()`` runs
standalone (Tune wraps a trainer as a trainable instead — the dependency
points the other way, which keeps the stack usable without Tune).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.checkpoint_manager import CheckpointManager
from ray_tpu.air.config import RunConfig, ScalingConfig
from ray_tpu.air.result import Result
from ray_tpu.train.backend import BackendConfig, JaxConfig
from ray_tpu.train.backend_executor import (
    BackendExecutor,
    TrainingWorkerError,
)
from ray_tpu.util import tracing


class TrainingFailedError(RuntimeError):
    pass


def _write_timeline(exp_dir: str, trace_id: str, executor: BackendExecutor):
    """``<exp_dir>/timeline.json``: ``{"spans", "counters", "dropped"}``,
    the spans as the tracing layer records them
    (``trace_analysis.to_chrome_trace`` turns them into a Perfetto file).
    This process's part (the driver's spans and, in single-node mode, the
    raylet's worker spawns, joined by pid), the parts the workers handed
    back, and what a raylet in a process of its own put into the GCS trace
    table under the job's trace.  Never fails the job."""
    try:
        parts = [tracing.timeline_take(trace_id, executor.worker_pids)]
        parts += executor.timelines
        try:
            from ray_tpu.util import state

            parts.append({"spans": [
                sp for sp in state.get_trace(trace_id)["spans"]
                if sp["name"] == "raylet.worker_spawn"]})
        except Exception:  # noqa: BLE001 - no runtime, no table
            pass
        os.makedirs(exp_dir, exist_ok=True)
        tmp = os.path.join(exp_dir, f".timeline.{os.getpid()}.tmp")
        with open(tmp, "w") as f:
            json.dump(tracing.timeline_merge(parts), f)
        os.replace(tmp, os.path.join(exp_dir, "timeline.json"))
    except Exception:  # noqa: BLE001
        pass


class DataParallelTrainer:
    """SPMD training: the same ``train_loop_per_worker`` on N workers.

    With a JaxConfig backend the workers form ONE global device mesh
    (multi-process jax.distributed), so "data parallel" here covers every
    jax sharding the loop chooses — dp/fsdp/tp/sp/ep are all expressible
    inside the loop via ShardingConfig over ``jax.devices()``.
    """

    _default_backend_config: BackendConfig = BackendConfig()

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        backend_config: Optional[BackendConfig] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self._train_loop = train_loop_per_worker
        self._train_loop_config = train_loop_config
        self._backend_config = backend_config or self._default_backend_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig(
            name=f"train_{time.strftime('%Y%m%d-%H%M%S')}"
        )
        if self.run_config.name is None:
            self.run_config.name = f"train_{time.strftime('%Y%m%d-%H%M%S')}"
        self._datasets = datasets or {}
        self._resume_from_checkpoint = resume_from_checkpoint

    # ------------------------------------------------------------------

    def _dataset_splitter(self):
        """Returns a callable that splits registered datasets into
        per-rank shard dicts (ray_tpu.data integration)."""
        if not self._datasets:
            return None
        datasets = self._datasets

        def split(world_size: int):
            from ray_tpu.data.dataset import Dataset
            from ray_tpu.data.iterator import DataIterator

            shards_per_rank = [dict() for _ in range(world_size)]
            for name, ds in datasets.items():
                if hasattr(ds, "streaming_split"):
                    # disjoint STREAMED shards — blocks are claimed from a
                    # coordinator as each worker consumes, never sliced up
                    # front (reference: stream_split_iterator.py, the
                    # reference's default Train ingest).  NOTE the shard is
                    # a consume-style iterator: count()/materialize() are
                    # unavailable on it (its share is decided by the pull
                    # loop) — loops needing a static count should count the
                    # dataset before passing it in.
                    try:
                        parts = ds.streaming_split(world_size)
                    except ValueError:  # actor-compute chain: static split
                        parts = ds.split(world_size)
                elif hasattr(ds, "split"):
                    parts = ds.split(world_size)
                else:  # plain list/iterable: round-robin
                    parts = [ds] * world_size
                for rank in range(world_size):
                    shard = parts[rank]
                    if isinstance(shard, Dataset):
                        # workers consume shards through the iterator API
                        # (reference: session.get_dataset_shard returns a
                        # DataIterator, `python/ray/data/iterator.py`)
                        shard = DataIterator(shard)
                    shards_per_rank[rank][name] = shard
            return shards_per_rank

        return split

    def fit(self) -> Result:
        """Runs the job, and leaves its timeline in the run directory
        (``<Result.path>/timeline.json``) whatever ``RAY_TPU_TRACE`` says
        and however the job ends: the root span ``train.fit`` and, beneath
        it, what the driver, the raylet and the workers spent the wall
        time on (README, "Tracing")."""
        sc = self.scaling_config
        rc = self.run_config
        exp_dir = rc.resolved_storage_path()

        if isinstance(self._backend_config, JaxConfig) and \
                sc.devices_per_worker and \
                self._backend_config.devices_per_worker is None:
            self._backend_config.devices_per_worker = sc.devices_per_worker

        executor = BackendExecutor(
            self._backend_config,
            num_workers=sc.num_workers,
            resources_per_worker=sc._resources_per_worker_not_none,
            experiment_name=rc.name or "",
        )
        job = tracing.timeline_span("train.fit", root=True,
                                    experiment=rc.name or "",
                                    workers=sc.num_workers)
        try:
            with job:
                result = self._run(executor, exp_dir)
                if result.error is not None:
                    job.set_error(repr(result.error))
        finally:
            _write_timeline(exp_dir, job.trace_id, executor)
        if result.error is not None and \
                not isinstance(result.error, TrainingFailedError):
            raise result.error
        return result

    def _run(self, executor: BackendExecutor, exp_dir: str) -> Result:
        rc = self.run_config
        ckpt_mgr = CheckpointManager(exp_dir, rc.checkpoint_config)
        max_failures = rc.failure_config.max_failures
        failures = 0
        latest_checkpoint: Optional[Checkpoint] = self._resume_from_checkpoint
        metrics_history = []
        last_metrics: Optional[dict] = None
        error: Optional[BaseException] = None

        executor.start()
        started = False
        rounds = 0
        try:
            while True:
                try:
                    if not started:
                        executor.start_training(
                            self._train_loop, self._train_loop_config,
                            checkpoint=latest_checkpoint,
                            dataset_splitter=self._dataset_splitter(),
                        )
                        started = True
                    # the driver's side of one lockstep round: until a
                    # restart, `n` is the `n` of the reports it consumes
                    with tracing.timeline_span("train.round", n=rounds):
                        round_results = executor.get_next_results()
                    rounds += 1
                except TrainingWorkerError as e:
                    failures += 1
                    if max_failures >= 0 and failures > max_failures:
                        error = TrainingFailedError(
                            f"worker group failed {failures}x "
                            f"(max_failures={max_failures}): {e}"
                        )
                        break
                    # Restart from the latest checkpoint (reference
                    # `backend_executor.py:625`).
                    latest_checkpoint = (ckpt_mgr.latest.checkpoint
                                         if ckpt_mgr.latest
                                         else latest_checkpoint)
                    with tracing.timeline_span("train.restart",
                                               failures=failures,
                                               cause=str(e)[:200]):
                        executor.restart()
                    started = False
                    continue
                if round_results is None:
                    break
                # rank-0's metrics are canonical (reference takes worker 0)
                rank0 = round_results[0]
                last_metrics = rank0["metrics"]
                metrics_history.append(last_metrics)
                ckpt = next((r["checkpoint"] for r in round_results
                             if r["checkpoint"] is not None), None)
                if ckpt is not None:
                    # bookkeeping that delays the next round
                    with tracing.timeline_span("train.checkpoint_register"):
                        tracked = ckpt_mgr.register(ckpt, last_metrics)
                    latest_checkpoint = tracked.checkpoint
        except BaseException as e:  # noqa: BLE001 - user loop error
            error = e
        finally:
            executor.shutdown(graceful=error is None)

        return Result(
            metrics=last_metrics,
            checkpoint=ckpt_mgr.latest.checkpoint if ckpt_mgr.latest
            else latest_checkpoint,
            error=error,
            metrics_history=metrics_history,
            path=exp_dir,
        )

    # Tune integration: a trainer is convertible to a trainable function.
    def as_trainable(self) -> Callable:
        trainer = self

        def trainable(config: Optional[dict] = None):
            from ray_tpu.train import session as tune_session

            merged = dict(trainer._train_loop_config or {})
            if config:
                merged.update(config)
            trainer2 = trainer.__class__(
                trainer._train_loop,
                train_loop_config=merged,
                backend_config=trainer._backend_config,
                scaling_config=trainer.scaling_config,
                run_config=trainer.run_config,
                datasets=trainer._datasets,
                resume_from_checkpoint=tune_session.get_checkpoint(),
            )
            result = trainer2.fit()
            if result.metrics is not None:
                tune_session.report(result.metrics)

        trainable.__name__ = f"{type(self).__name__}_trainable"
        return trainable


class JaxTrainer(DataParallelTrainer):
    """DataParallelTrainer with the JAX multi-process mesh bootstrap on by
    default (the ``TorchTrainer``-analogue for the TPU world)."""

    _default_backend_config = JaxConfig()

    def __init__(self, train_loop_per_worker, *, jax_config: Optional[JaxConfig] = None,
                 **kwargs):
        kwargs.setdefault("backend_config", jax_config or JaxConfig())
        super().__init__(train_loop_per_worker, **kwargs)
