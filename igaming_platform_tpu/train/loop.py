"""Continuous training loop: train -> checkpoint -> hot-swap into serving.

The same-pod refresh cycle the north star requires (SURVEY.md §2.2): a
background trainer periodically checkpoints (Orbax) and swaps fresh params
into a live TPUScoringEngine — replacing the reference's offline
train -> ONNX export -> container redeploy cycle with an in-process,
version-keyed handoff. Also restores from the latest checkpoint on start
(crash/preemption resume, SURVEY.md §5 "Checkpoint / resume").
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass

from igaming_platform_tpu.train.checkpoint import restore_trainer, save_checkpoint
from igaming_platform_tpu.train.data import make_stream
from igaming_platform_tpu.train.trainer import TrainConfig, Trainer

logger = logging.getLogger(__name__)


@dataclass
class LoopConfig:
    checkpoint_dir: str = "checkpoints"
    checkpoint_every: int = 500  # steps
    swap_every: int = 100  # steps
    max_steps: int | None = None


class TrainingLoop:
    """Background trainer with checkpointing and live param swaps."""

    def __init__(
        self,
        trainer: Trainer | None = None,
        *,
        engine=None,  # TPUScoringEngine with ml_backend="multitask", or None
        config: LoopConfig | None = None,
        train_config: TrainConfig | None = None,
    ):
        self.trainer = trainer or Trainer(train_config)
        self.engine = engine
        self.config = config or LoopConfig()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.last_metrics: dict[str, float] = {}
        self.swaps = 0
        self.checkpoints = 0

        if restore_trainer(self.trainer, self.config.checkpoint_dir):
            logger.info("resumed from checkpoint at step %d", self.trainer.state.step)

    def run_steps(self, steps: int) -> dict[str, float]:
        """Synchronous loop body (tests / foreground use).

        Runs the trainer's pipelined path: the next batch's H2D overlaps
        the current step and metrics stay on device, materialized (one
        packed transfer) only every few steps and at swap/checkpoint
        boundaries — a per-step scalar readback is a host sync that
        stalls the dispatch queue every step.
        """
        if steps <= 0:
            return self.last_metrics
        data = make_stream(self.trainer.cfg.batch_size, seed=self.trainer.cfg.seed + self.trainer.state.step)
        pending = self.trainer.put_batch(next(data))
        metrics_dev = None
        materialized = True
        for i in range(steps):
            if self._stop.is_set():
                break
            current = pending
            if i + 1 < steps:
                pending = self.trainer.put_batch(next(data))
            metrics_dev = self.trainer.train_step_device(current)
            materialized = False
            step = self.trainer.state.step
            at_swap = self.config.swap_every and step % self.config.swap_every == 0
            at_ckpt = (self.config.checkpoint_every
                       and step % self.config.checkpoint_every == 0)
            if at_swap or at_ckpt or i + 1 >= steps or i % 10 == 0:
                self.last_metrics = self.trainer.materialize_metrics(metrics_dev)
                materialized = True
            if at_swap:
                self._swap()
            if at_ckpt:
                save_checkpoint(self.config.checkpoint_dir, self.trainer.state)
                self.checkpoints += 1
        if metrics_dev is not None and not materialized:
            # A stop() mid-stride must not leave last_metrics stale: the
            # final computed step's metrics are already on device.
            self.last_metrics = self.trainer.materialize_metrics(metrics_dev)
        return self.last_metrics

    def _swap(self) -> None:
        if self.engine is not None:
            self.engine.swap_params({"multitask": self.trainer.export_params()})
            self.swaps += 1

    def start(self) -> "TrainingLoop":
        def body():
            steps = self.config.max_steps or (1 << 62)
            self.run_steps(steps)

        self._thread = threading.Thread(target=body, name="training-loop", daemon=True)
        self._thread.start()
        return self

    def stop(self, save: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
        if save:
            save_checkpoint(self.config.checkpoint_dir, self.trainer.state)
            self.checkpoints += 1
