"""Hydra's legacy user-facing API (paper Fig. 4):

    task_0 = ModelTask(cfg_0, dataloader_0, lr_0, epochs_0)
    task_1 = ModelTask(cfg_1, dataloader_1, lr_1, epochs_1)
    orchestra = ModelOrchestrator([task_0, task_1], hydra_cfg)
    report = orchestra.train_models()

Since the unified session API landed (``repro.api`` / docs/api.md), both
classes here are thin wrappers: ``ModelOrchestrator`` delegates to a
``Session`` holding one ``TrainJob`` per task, and ``SpilledInference``
mirrors what an ``EvalJob`` runs per batch.  The call signatures and
semantics are unchanged — partitioning (Algorithm 1), spilling, SHARP
scheduling (Sharded-LRTF), and double buffering all happen below the line.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partitioner as pt
from repro.core import shard_graph as sg
from repro.core.sharp import HydraConfig, RunReport, ShardFunctions
from repro.core.spilling import HostModelStore
from repro.optim import optimizers as opt

_warned = False


def _deprecate_once(old: str, new: str) -> None:
    global _warned
    if _warned:
        return
    _warned = True
    warnings.warn(f"{old} is a compatibility shim over {new}; "
                  f"prefer {new} for new code (see docs/api.md)",
                  DeprecationWarning, stacklevel=3)


@dataclass
class ModelTask:
    """One model-selection candidate: architecture + data + hyperparams."""
    cfg: Any                                   # ArchConfig
    dataloader: Iterator[dict]
    lr: float = 1e-3
    epochs: int = 1
    steps_per_epoch: int = 4
    optimizer: str = "adamw"
    params: Optional[Any] = None               # init'd if None
    seed: int = 0
    batch: int = 2                              # partitioning pilot shape
    seq: int = 128
    # AutoML early stopping (Hyperband-class; paper §4.7.2's trigger for
    # LRTF's graceful case-1 -> case-2 degradation): called with the loss
    # history at each mini-batch boundary; return True to stop the model.
    early_stop: Optional[Callable[[list], bool]] = None

    def opt_config(self) -> opt.OptimizerConfig:
        # NOTE: per-shard stepping composes exactly with sequential training
        # only when gradient clipping is off (clipping needs the global norm,
        # which no single shard sees).  Hydra therefore disables it.
        return opt.OptimizerConfig(kind=self.optimizer, lr=self.lr,
                                   grad_clip=0.0)


class ModelOrchestrator:
    """Automated multi-model trainer — now a thin wrapper holding a
    ``repro.api.Session`` with one ``TrainJob`` per task.  ``models`` and
    the report shape are unchanged, so existing callers keep working."""

    def __init__(self, tasks: list[ModelTask],
                 hydra_cfg: Optional[HydraConfig] = None):
        from repro.api import Session, TrainJob
        _deprecate_once("ModelOrchestrator", "repro.api.Session")
        self.tasks = tasks
        self.session = Session(hydra_cfg)
        self.hc = self.session.hc
        for task in tasks:
            self.session.submit(TrainJob.from_task(task))
        # materialize eagerly: callers inspect .models before training
        self.models = self.session.train_execs

    def train_models(self, *, max_units: Optional[int] = None) -> RunReport:
        report = self.session.run(max_units=max_units)
        return report.train

    def model_params(self, model_id: int):
        return self.models[model_id].store.model_params()


# ---------------------------------------------------------------------------
# large-model inference via spilling (paper §6 "Large Model Inference":
# "model spilling, automated partitioning, and automated shard orchestration
# all suffice already for out-of-the-box large model inference")
# ---------------------------------------------------------------------------


def spilled_forward(store, fns, partition, batch, *, on_shard=None):
    """Forward-only shard queue: promote each shard, apply it, thread the
    boundary activation — shared by ``SpilledInference`` and the session
    API's ``EvalJob``.  Returns ``(logits, bytes_moved)``; ``on_shard``
    fires after each shard unit (the session ticks serve engines there)."""
    batch = jax.tree.map(jnp.asarray, batch)
    act: dict = {}
    moved = 0
    for shard in partition.shards:
        own, shared, _ = store.promote_shard(shard)
        moved += store.shard_transfer_bytes(shard, train=False)
        out = fns.fwd(shard)(own, shared, act, batch)[0]
        act = out
        if on_shard is not None:
            on_shard(shard)
    return act["logits"], moved


class SpilledInference:
    """Forward-only execution of a larger-than-device model through the
    shard queue: each shard's params are promoted, applied, and demoted —
    a model bounded only by host DRAM runs inference on one device.

        infer = SpilledInference(cfg, params, device_budget_bytes=...)
        logits = infer(batch)
    """

    def __init__(self, cfg, params, *, device_budget_bytes: int,
                 batch: int = 2, seq: int = 128,
                 buffer_frac: float = 0.05):
        from repro.models import api
        self.cfg = cfg
        self.plan = sg.build_plan(cfg)
        host = sg.prepare_host_params(cfg, jax.tree.map(np.array, params))
        self.partition = pt.partition(
            cfg, host, self.plan, budget_bytes=device_budget_bytes,
            batch=batch, seq=seq, buffer_frac=buffer_frac, train=False)
        # inference transfers exclude grads/optimizer state
        self.store = HostModelStore(cfg, self.plan, params,
                                    opt.OptimizerConfig(grad_clip=0.0),
                                    self.partition)
        self.fns = ShardFunctions(cfg, self.plan, self.partition,
                                  opt.OptimizerConfig(grad_clip=0.0))
        self.bytes_moved = 0

    @property
    def n_shards(self) -> int:
        return len(self.partition.shards)

    def __call__(self, batch):
        """batch -> logits, running the shard queue forward-only."""
        logits, moved = spilled_forward(self.store, self.fns,
                                        self.partition, batch)
        self.bytes_moved += moved
        return logits

    def loss(self, batch):
        logits = self(batch)
        from repro.training.losses import softmax_xent
        return softmax_xent(logits, batch["labels"])


# ---------------------------------------------------------------------------
# sequential reference (the "no effect on accuracy" oracle)
# ---------------------------------------------------------------------------

def train_sequential_reference(task: ModelTask) -> tuple[Any, list]:
    """Plain jit'd full-model training — Hydra must reproduce its losses."""
    from repro.models import api
    from repro.training import make_train_step
    cfg = task.cfg
    params = task.params if task.params is not None else \
        api.init_params(cfg, jax.random.PRNGKey(task.seed))
    ocfg = task.opt_config()
    state = opt.init_state(ocfg, params)
    step = jax.jit(make_train_step(cfg, ocfg))
    losses = []
    it = iter(task.dataloader)
    for _ in range(task.epochs * task.steps_per_epoch):
        batch = jax.tree.map(jnp.asarray, next(it))
        params, state, metrics = step(params, state, batch)
        losses.append(float(metrics["loss"]))
    return params, losses
