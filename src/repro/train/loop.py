"""The training driver: data → jit'd step → checkpoints → fault handling.

Wiring of every fault-tolerance feature:
* atomic/async checkpoints every ``ckpt_every`` steps + at exit,
* preemption: SIGTERM/SIGINT set a flag checked at step boundaries (the
  by_blocks interruption point) → final checkpoint → clean exit,
* straggler telemetry: per-step times feed the AdaptiveRebalancer (host-side
  shares) and the StragglerDetector (elastic eviction escalations),
* resumability: pipeline state (a counter) rides in the checkpoint extras.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from ..configs.base import ModelConfig
from ..data.pipeline import DataConfig, DataPipeline, host_batch_to_device
from ..dist.sharding import current_ctx
from ..models.model import Model
from ..optim.adamw import AdamWConfig, init_state
from .checkpoint import CheckpointManager, config_fingerprint
from .step import TrainState, make_train_step, train_state_shardings
from .straggler import AdaptiveRebalancer, StragglerDetector, TelemetryBuffer


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "checkpoints"
    keep: int = 3
    log_every: int = 10
    num_microbatches: int = 1
    num_replicas: int = 1          # telemetry granularity (DP replicas)
    ckpt_retries: int = 2          # transient-I/O retries per checkpoint
    ckpt_backoff_s: float = 0.0    # base retry backoff (doubles per attempt)


class Trainer:
    def __init__(self, model: Model, opt_cfg: AdamWConfig,
                 data_cfg: DataConfig, loop_cfg: LoopConfig, *,
                 step_fn: Optional[Callable] = None,
                 batch_shardings: Any = None):
        self.model = model
        self.opt_cfg = opt_cfg
        self.loop_cfg = loop_cfg
        self.pipeline = DataPipeline(data_cfg)
        self.batch_shardings = batch_shardings
        self._step = step_fn or make_train_step(
            model, opt_cfg, num_microbatches=loop_cfg.num_microbatches)
        self.step_fn = jax.jit(self._step, donate_argnums=0)
        fp = config_fingerprint({
            "model": dataclasses.asdict(model.cfg),
            "opt": dataclasses.asdict(opt_cfg)})
        self.ckpt = CheckpointManager(loop_cfg.ckpt_dir, keep=loop_cfg.keep,
                                      fingerprint=fp,
                                      retries=loop_cfg.ckpt_retries,
                                      backoff_s=loop_cfg.ckpt_backoff_s)
        self.telemetry = TelemetryBuffer(loop_cfg.num_replicas)
        self.rebalancer = AdaptiveRebalancer(loop_cfg.num_replicas)
        self.detector = StragglerDetector()
        self._preempted = False
        self.metrics_log: list = []

    # ----------------------------------------------------------- lifecycle
    def install_signal_handlers(self):
        def handler(signum, frame):
            self._preempted = True
        signal.signal(signal.SIGTERM, handler)
        signal.signal(signal.SIGINT, handler)

    def _fresh_state(self) -> TrainState:
        params = self.model.init(jax.random.PRNGKey(0))
        return TrainState(params=params, opt=init_state(self.opt_cfg, params))

    def init_or_restore(self) -> TrainState:
        ctx = current_ctx()
        if ctx is None:
            state = self._fresh_state()
        else:
            # made in place on the mesh with the rule table's shardings (no
            # device ever holds the whole state), and kept in them by every
            # step, so the step compiles once
            shardings = train_state_shardings(self.model.cfg, self.model,
                                              self.opt_cfg, ctx.mesh)
            state = jax.jit(self._fresh_state, out_shardings=shardings)()
            self.step_fn = jax.jit(self._step, donate_argnums=0,
                                   out_shardings=(shardings, None))
        latest = self.ckpt.latest_step()
        if latest is not None:
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            state, extra = self.ckpt.restore(abstract)
            self.pipeline.state.step = int(extra.get("data_step", 0))
            self.start_step = latest
        else:
            self.start_step = 0
        return state

    def save(self, step: int, state: TrainState, blocking=False):
        self.ckpt.save(step, state,
                       extra={"data_step": self.pipeline.state.step},
                       blocking=blocking)

    # ----------------------------------------------------------------- run
    def run(self, state: Optional[TrainState] = None, *,
            on_step: Optional[Callable[[int, TrainState], None]] = None
            ) -> TrainState:
        """Run the loop.  ``on_step(step, state)`` fires after every completed
        step, before checkpointing — the chaos harness injects faults (SIGTERM,
        host death) there; anything it raises or signals is then handled at
        the step boundary, the by_blocks interruption point."""
        lc = self.loop_cfg
        if state is None:
            state = self.init_or_restore()
        step = getattr(self, "start_step", 0)
        while step < lc.total_steps and not self._preempted:
            batch = host_batch_to_device(self.pipeline.next_batch(),
                                         self.batch_shardings)
            t0 = time.time()
            state, metrics = self.step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.time() - t0
            step += 1
            if on_step is not None:
                on_step(step, state)
            self.telemetry.record(step % lc.num_replicas, dt)
            shares = self.rebalancer.maybe_rebalance(self.telemetry)
            evict = self.detector.check(self.telemetry)
            if step % lc.log_every == 0 or step == lc.total_steps:
                row = {"step": step,
                       "loss": float(metrics["loss"]),
                       "grad_norm": float(metrics["grad_norm"]),
                       "lr": float(metrics["lr"]),
                       "step_time_s": round(dt, 4)}
                if shares is not None:
                    row["rebalanced_shares"] = [round(s, 3) for s in shares]
                if evict is not None:
                    row["evict_candidate"] = evict
                self.metrics_log.append(row)
                print(f"[train] {row}", flush=True)
            if step % lc.ckpt_every == 0:
                self.save(step, state)
        # final (or preemption) checkpoint
        self.save(step, state, blocking=True)
        if self._preempted:
            print(f"[train] preempted at step {step}; checkpoint saved.",
                  flush=True)
        return state


__all__ = ["Trainer", "LoopConfig"]
