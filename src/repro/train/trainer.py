"""Fault-tolerant training loop.

Composes the jitted train step with:

* periodic + async checkpointing (restart-safe, elastic restore),
* **network-adaptive synchronization**: the trainer subscribes to a
  ``repro.control.ControlPlane`` — the same instance the WAN plane can
  observe.  On :class:`~repro.control.events.RelayOrderChanged` (or any
  event the configured ``device_sync`` strategy declares a reaction to in
  the registry) it rebuilds the jitted step with the new ``relay_psum``
  ring order / :class:`SyncConfig`.  Sustained straggler trips feed
  ``ControlPlane.force_replan`` — the immediate, event-driven replan path,
* **failure handling**: a step that raises (device loss) rolls back to the
  last checkpoint; duplicate replays are harmless because the optimizer
  state is versioned by ``step`` (applying the same step twice from the same
  checkpoint is deterministic and idempotent at the state level).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .. import checkpoint as _ckpt_pkg  # noqa: F401  (namespace)
from ..checkpoint.checkpoint import latest_step, restore, save, save_async
from ..configs.base import ModelConfig
from ..data.pipeline import DataConfig, SyntheticLM
from ..dist.collectives import exchange_local_share
from ..models.model import init_params
from ..optim.adamw import adamw_init
from .train_step import TrainConfig, abstract_residuals, build_train_step

__all__ = ["TrainerConfig", "Trainer", "StragglerMonitor"]


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: str | None = None
    ckpt_every: int = 50
    ckpt_async: bool = True
    log_every: int = 10
    seed: int = 0
    straggler_threshold: float = 1.5   # step time vs EWMA
    straggler_sustain: int = 3
    control_every: int = 1             # pump the ControlPlane every N steps


class StragglerMonitor:
    """EWMA step-time tracker with sustained-deviation detection —
    the same damping policy as the WAN replanner (Sec 4.2)."""

    def __init__(self, threshold: float = 1.5, sustain: int = 3, alpha: float = 0.2):
        self.threshold = threshold
        self.sustain = sustain
        self.alpha = alpha
        self.ewma: float | None = None
        self._over = 0
        self.trips = 0

    def observe(self, dt: float) -> bool:
        """Feed one step time; returns True when mitigation should trigger."""
        if self.ewma is None:
            self.ewma = dt
            return False
        trigger = False
        if dt > self.threshold * self.ewma:
            self._over += 1
            if self._over >= self.sustain:
                trigger = True
                self.trips += 1
                self._over = 0
        else:
            self._over = 0
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return trigger


class Trainer:
    def __init__(
        self,
        model_cfg: ModelConfig,
        mesh,
        tcfg: TrainConfig,
        run_cfg: TrainerConfig,
        data_cfg: DataConfig | None = None,
        *,
        control: "Any | None" = None,
    ):
        """``control`` is a ``repro.control.ControlPlane``; the trainer
        subscribes for network events and, when the plane carries its own
        ``NetworkView``, pumps one control round every
        ``run_cfg.control_every`` steps.  A plane without a view (shared
        with a WAN-plane driver) is subscribe-only."""
        self.model_cfg = model_cfg
        self.mesh = mesh
        self.tcfg = tcfg
        self.run_cfg = run_cfg
        self.data_cfg = data_cfg or DataConfig(
            vocab_size=model_cfg.vocab_size, seq_len=128, global_batch=8,
            seed=run_cfg.seed,
        )
        self.data = SyntheticLM(self.data_cfg)
        self.make_jit, self.shardings = build_train_step(model_cfg, mesh, tcfg)
        self.monitor = StragglerMonitor(
            run_cfg.straggler_threshold, run_cfg.straggler_sustain
        )
        self.control = control
        self.network_events: list[Any] = []
        self.sync_rebuilds = 0
        self.compiles = 0    # freshly built steps called (each may compile)
        self.rollbacks = 0   # recoverable faults rolled back to a checkpoint
        if control is not None:
            control.subscribe(self._on_network_event)
        self._pending_save = None
        self.history: list[dict[str, Any]] = []

        # created in place at their shardings: nothing is built whole on
        # one device and resharded afterwards
        sh = self.shardings
        init = jax.jit(
            functools.partial(_init_state, model_cfg, tcfg, mesh.shape.get("pod", 1)),
            out_shardings=(sh["params"], sh["opt"], sh["residuals"]),
        )
        self.params, self.opt_state, self.residuals = init(
            jax.random.PRNGKey(run_cfg.seed)
        )
        # share of the gradient's elements the pod exchange takes on each
        # chip's own shard (0 with one pod)
        self.exchange_local_share = exchange_local_share(self.params,
                                                         sh["exchange"])
        if sh["exchange"] is not None:
            print(f"pod exchange: {self.exchange_local_share:.4f} of the "
                  "gradient's elements on each chip's own shard")
        self.step_idx = 0
        self._step_fn = None

    # -- control-plane plumbing --------------------------------------------------

    def _on_network_event(self, event) -> None:
        """Apply the configured strategy's declared reaction to a network
        event: an updated ``SyncConfig`` rebuilds the jitted step (new
        relay ring order, density, ...); ``None`` means no reaction."""
        self.network_events.append(event)
        spec = self.tcfg.sync.spec
        if spec.react is None:
            return
        new_sync = spec.react(self.tcfg.sync, event)
        if new_sync is None or new_sync == self.tcfg.sync:
            return
        n_pods = self.mesh.shape.get("pod", 1)
        if new_sync.ring_order is not None and len(new_sync.ring_order) != n_pods:
            return  # event from a view whose nodes are not this mesh's pods
        self.tcfg = dataclasses.replace(self.tcfg, sync=new_sync)
        self.make_jit, self.shardings = build_train_step(
            self.model_cfg, self.mesh, self.tcfg
        )
        self._step_fn = None  # recompile with the new collective program
        self.sync_rebuilds += 1

    # -- checkpoint plumbing ---------------------------------------------------

    def _state(self):
        st = {"params": self.params, "opt": self.opt_state, "step": self.step_idx}
        if self.residuals is not None:
            st["residuals"] = self.residuals
        return st

    def save_ckpt(self):
        if self.run_cfg.ckpt_dir is None:
            return
        if self._pending_save is not None:
            self._pending_save.join()
        st = self._state()
        if self.run_cfg.ckpt_async:
            self._pending_save = save_async(self.run_cfg.ckpt_dir, self.step_idx, st)
        else:
            save(self.run_cfg.ckpt_dir, self.step_idx, st)

    def maybe_resume(self) -> bool:
        if self.run_cfg.ckpt_dir is None:
            return False
        last = latest_step(self.run_cfg.ckpt_dir)
        if last is None:
            return False
        like = self._state()
        st = restore(self.run_cfg.ckpt_dir, last, like)
        self.params = st["params"]
        self.opt_state = st["opt"]
        self.residuals = st.get("residuals", self.residuals)
        self.step_idx = int(st["step"])
        return True

    # -- main loop ---------------------------------------------------------------

    def _build(self, batch):
        if self._step_fn is None:
            self._step_fn = self.make_jit(batch)
        return self._step_fn

    def run(self, *, fault_injector: Callable[[int], None] | None = None):
        """Train until ``run_cfg.steps``.  Each iteration is a profiler step
        ``train`` holding host spans ``trainer.data``, ``trainer.put``,
        ``trainer.compile`` (first call of a freshly built step) or
        ``trainer.step``, ``trainer.wait``, ``trainer.control`` and
        ``trainer.checkpoint``, each tagged with its step."""
        cfg = self.run_cfg
        span = jax.profiler.TraceAnnotation
        while self.step_idx < cfg.steps:
            n = self.step_idx
            with jax.profiler.StepTraceAnnotation("train", step_num=n):
                t_data = time.perf_counter()
                with span("trainer.data", step=n):
                    host_batch = self.data.batch(n)
                with span("trainer.put", step=n):
                    batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
                data_s = time.perf_counter() - t_data
                fresh = self._step_fn is None
                step = self._build(batch)
                t0 = time.perf_counter()
                try:
                    if fault_injector is not None:
                        fault_injector(n)
                    with span("trainer.compile" if fresh else "trainer.step", step=n):
                        out = step(self.params, self.opt_state, self.residuals, batch)
                    self.params, self.opt_state, self.residuals, metrics = out
                    with span("trainer.wait", step=n):
                        jax.block_until_ready(metrics["loss"])
                        dt = time.perf_counter() - t0
                        loss = float(metrics["loss"])
                        grad_norm = float(metrics["grad_norm"])
                        counters = {k: float(metrics[k]) for k in _COUNTERS
                                    if k in metrics}
                except _RECOVERABLE:  # device failure: roll back + replay
                    resumed = self.maybe_resume()
                    if not resumed:
                        raise
                    self._step_fn = None  # rebuild on (possibly new) topology
                    self.rollbacks += 1
                    continue
                if fresh:
                    self.compiles += 1
                self.step_idx += 1
                rec = {
                    "step": self.step_idx,
                    "loss": loss,
                    "grad_norm": grad_norm,
                    "dt": dt,
                    "data_s": data_s,
                    "compiled": fresh,
                    **counters,
                }
                self.history.append(rec)
                with span("trainer.control", step=n):
                    if (
                        self.control is not None
                        and self.control.view is not None
                        and self.step_idx % max(1, cfg.control_every) == 0
                    ):
                        self.control.step()  # probe -> damped replan -> events
                    # a step that compiled says nothing about the device's pace
                    if (not fresh and self.monitor.observe(dt)
                            and self.control is not None):
                        # sustained step-time degradation: event-driven
                        # replan, effective immediately (not at the next
                        # observation)
                        self.control.force_replan(
                            reason=f"straggler@step{self.step_idx}"
                        )
                if cfg.ckpt_dir and self.step_idx % cfg.ckpt_every == 0:
                    with span("trainer.checkpoint", step=n):
                        self.save_ckpt()
            if self.step_idx % cfg.log_every == 0 or self.step_idx == cfg.steps:
                print(
                    f"step {rec['step']:5d}  loss {rec['loss']:.4f}  "
                    f"gnorm {rec['grad_norm']:.3f}  {dt*1e3:.0f} ms"
                    + "".join(f"  {k} {rec[k]:.4f}" for k in _COUNTERS if k in rec)
                )
        if self._pending_save is not None:
            self._pending_save.join()
        return self.history


def _init_state(model_cfg: ModelConfig, tcfg: TrainConfig, n_pods: int, key):
    """(params, optimizer state, residuals) of a fresh run; the residuals
    are zeros in the per-pod layout of ``abstract_residuals``."""
    params = jax.tree.map(
        lambda p: p.astype(tcfg.param_dtype), init_params(model_cfg, key)
    )
    residuals = jax.tree.map(
        lambda r: jnp.zeros(r.shape, r.dtype),
        abstract_residuals(model_cfg, tcfg, n_pods),
    )
    return params, adamw_init(params, tcfg.optim), residuals


# the train step's per-step counters of a model whose MoE layers record
# their routing (``train_step.routing_counters``), copied into the history
_COUNTERS = ("moe_load_peak", "moe_drop_share")


class FaultInjected(RuntimeError):
    """Raised by test fault injectors to simulate a device failure."""


_RECOVERABLE = (FaultInjected,)
