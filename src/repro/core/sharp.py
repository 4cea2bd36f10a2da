"""SHARP — Shard Alternator Parallelism (paper §4.4–4.6).

The executor interleaves *shard units* (forward or backward of one shard of
one model on one mini-batch) from many models across devices, subject to each
model's sequential dependency.  Real JAX compute runs for every unit; device
parallelism is *virtualized*: each device owns a clock, and unit/transfer
durations (measured compute + modeled host-link transfers) advance it.  On a
real multi-accelerator fleet the same event loop dispatches to concurrent
device streams; on this 1-CPU container the timeline is exact but serialized.

Double buffering (§4.6): when a device *starts* a unit, the scheduler
immediately picks that device's next unit and begins promoting its shard into
the reserved buffer region — the transfer overlaps compute and is hidden iff
transfer_time <= compute_time.  The serendipitous bonus: if the next unit is
the same model's successor on the same device, the boundary activation never
moves.
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import scheduler as sched
from repro.core import shard_graph as sg
from repro.core.partitioner import PartitionResult, Shard, tree_bytes
from repro.core.spilling import DeviceMemory, HostModelStore
from repro.optim import optimizers as opt


@dataclass
class HydraConfig:
    n_devices: int = 8
    device_budget_bytes: int = 11 * 10**9      # paper's RTX 2080 Ti
    buffer_frac: float = 0.05                  # double-buffer loading zone
    link_bw: float = 16e9                      # host<->device B/s (PCIe3 x16)
    enable_sharp: bool = True                  # False -> one model at a time
    enable_double_buffer: bool = True
    scheduler: str = "lrtf"
    seed: int = 0
    partition_oracle: str = "analytic"
    pilot: bool = True                         # measured pilot pass
    # deterministic simulation: pin every unit's fwd/bwd runtime to this
    # value after the pilot (compiled programs still warm up and real
    # compute still runs).  Makespan comparisons then depend only on the
    # scheduling/transfer model, not on pilot-measurement noise — the
    # double-buffer regression test needs this on shared CPU runners.
    fixed_unit_runtime: Optional[float] = None
    # elasticity (paper §4.7: devices may disappear due to faults or get
    # added due to elasticity): device_id -> (available_from, available_until)
    # in virtual seconds; None = always available
    device_windows: Optional[dict] = None

    def validate(self) -> "HydraConfig":
        """Fail fast on configs that would otherwise die deep inside the
        partitioner or event loop.  repro.api.Session calls this on entry."""
        if self.n_devices < 1:
            raise ValueError(
                f"n_devices={self.n_devices}: need at least one device")
        if self.device_budget_bytes <= 0:
            raise ValueError(
                f"device_budget_bytes={self.device_budget_bytes}: must be a "
                "positive byte count (e.g. 11*10**9 for an RTX 2080 Ti)")
        if not 0.0 < self.buffer_frac <= 0.5:
            raise ValueError(
                f"buffer_frac={self.buffer_frac}: the double-buffer loading "
                "zone must be in (0, 0.5] — the paper finds ~0.05 suffices; "
                "above 0.5 the buffer would outsize the active region")
        if self.link_bw <= 0:
            raise ValueError(
                f"link_bw={self.link_bw}: host<->device bandwidth must be "
                "positive B/s (e.g. 16e9 for PCIe3 x16)")
        if self.scheduler not in sched.SCHEDULERS:
            raise ValueError(
                f"unknown scheduler {self.scheduler!r}: choose one of "
                f"{sorted(sched.SCHEDULERS)}")
        if self.partition_oracle not in ("analytic", "probe"):
            raise ValueError(
                f"unknown partition_oracle {self.partition_oracle!r}: "
                "choose 'analytic' or 'probe'")
        return self


@dataclass
class Unit:
    model_id: int
    shard: Shard
    direction: str        # "fwd" | "bwd"
    minibatch: int
    epoch: int


# compiled shard programs shared across ModelExecs with identical
# (cfg, shard-range), and steps across identical (cfg, optimizer) —
# model-selection jobs train many clones of one architecture, and
# recompiling per clone dominated benchmark wall time
_FN_CACHE: dict = {}


class ShardFunctions:
    """Compiled fwd/bwd/step programs per shard of one model."""

    def __init__(self, cfg, plan: sg.ShardPlan, partition: PartitionResult,
                 opt_cfg: opt.OptimizerConfig):
        self.cfg = cfg
        self.plan = plan
        self.partition = partition
        self.opt_cfg = opt_cfg
        self._fwd = {}
        self._bwd = {}
        step_key = (cfg, opt_cfg, "step")
        if step_key not in _FN_CACHE:
            _FN_CACHE[step_key] = jax.jit(self._step_impl)
        self._step = _FN_CACHE[step_key]

    def _chain(self, shard: Shard, own, shared, act, batch):
        """The shard's segments in order -> (act, counts).  A segment may
        add program counters to its act under ``"counts"``; they leave the
        chain here, summed over the shard, and never flow into the next
        segment or the backward pass."""
        counts = None
        for k, i in enumerate(range(shard.seg_lo, shard.seg_hi)):
            seg = self.plan.segments[i]
            seg_shared = {n: shared[n] for n in seg.shared}
            act = seg.apply(self.cfg, own[k], seg_shared, act, batch)
            if "counts" in act:
                act = dict(act)
                c = act.pop("counts")
                counts = c if counts is None else jax.tree.map(
                    jnp.add, counts, c)
        return act, counts

    def fwd(self, shard: Shard):
        if shard.index not in self._fwd:
            # forward/backward never read the optimizer config, so clones
            # that differ only in lr share them; only the step is per-opt
            key = (self.cfg, shard.seg_lo, shard.seg_hi,
                   "fwd", shard.index == len(self.partition.shards) - 1)
            if key not in _FN_CACHE:
                _FN_CACHE[key] = jax.jit(partial(self._fwd_impl, shard))
            self._fwd[shard.index] = _FN_CACHE[key]
        return self._fwd[shard.index]

    def _fwd_impl(self, shard, own, shared, act, batch):
        """-> (out, loss or None, counts or None)."""
        out, counts = self._chain(shard, own, shared, act, batch)
        if shard.index == len(self.partition.shards) - 1:
            loss = self.plan.loss(self.cfg, out, batch)
            return out, loss, counts
        return out, None, counts

    def bwd(self, shard: Shard):
        if shard.index not in self._bwd:
            last = shard.index == len(self.partition.shards) - 1
            key = (self.cfg, shard.seg_lo, shard.seg_hi, "bwd", last)
            if key not in _FN_CACHE:
                _FN_CACHE[key] = jax.jit(partial(
                    self._bwd_last_impl if last else self._bwd_impl, shard))
            self._bwd[shard.index] = _FN_CACHE[key]
        return self._bwd[shard.index]

    def _bwd_last_impl(self, shard, own, shared, act_in, batch):
        def f(o, s, a):
            out, _ = self._chain(shard, o, s, a, batch)
            return self.plan.loss(self.cfg, out, batch)

        loss, vjp = jax.vjp(f, own, shared, act_in)
        g_own, g_shared, g_act = vjp(jnp.ones_like(loss))
        return loss, g_own, g_shared, g_act

    def _bwd_impl(self, shard, own, shared, act_in, cot_out, batch):
        def f(o, s, a):
            return self._chain(shard, o, s, a, batch)[0]

        _, vjp = jax.vjp(f, own, shared, act_in)
        g_own, g_shared, g_act = vjp(cot_out)
        return g_own, g_shared, g_act

    def _step_impl(self, own, g_own, opt_state):
        return opt.update(self.opt_cfg, own, g_own, opt_state)


@dataclass
class ModelExec:
    """Execution state of one ModelTask inside the SHARP loop."""
    model_id: int
    cfg: Any
    plan: sg.ShardPlan
    partition: PartitionResult
    store: HostModelStore
    fns: ShardFunctions
    data_iter: Any
    epochs: int
    steps_per_epoch: int
    early_stop: Optional[Callable[[list], bool]] = None
    stopped_early: bool = False
    # dynamic state
    queue: list[Unit] = field(default_factory=list)
    cursor: int = 0
    epoch: int = 0
    minibatch: int = 0
    ready_at: float = 0.0
    reserved: bool = False
    act_location: Optional[int] = None     # device holding current activation
    current_batch: Any = None
    saved_acts: dict = field(default_factory=dict)   # shard_idx -> entry act
    saved_cot: Any = None                  # cotangent flowing backward
    losses: list = field(default_factory=list)
    done: bool = False
    # program counters of this step's forward units, and of steps whose
    # loss has been read (the executor books those in TransferStats)
    pending_counts: list = field(default_factory=list)
    ready_counts: list = field(default_factory=list)

    def build_minibatch_queue(self):
        shards = self.partition.shards
        units = [Unit(self.model_id, s, "fwd", self.minibatch, self.epoch)
                 for s in shards]
        units += [Unit(self.model_id, s, "bwd", self.minibatch, self.epoch)
                  for s in reversed(shards)]
        self.queue = units
        self.cursor = 0
        with TraceAnnotation("sharp.batch", step=self.minibatch,
                             model=self.model_id):
            self.current_batch = jax.tree.map(jnp.asarray,
                                              next(self.data_iter))

    def next_unit(self) -> Optional[Unit]:
        if self.done:
            return None
        if self.cursor >= len(self.queue):
            return None
        return self.queue[self.cursor]

    def minibatch_time(self) -> float:
        return sum(s.fwd_runtime + s.bwd_runtime for s in self.partition.shards)

    def progress(self) -> sched.ModelProgress:
        rem_units = self.queue[self.cursor:]
        rem_t = sum(u.shard.fwd_runtime if u.direction == "fwd"
                    else u.shard.bwd_runtime for u in rem_units)
        return sched.ModelProgress(
            model_id=self.model_id,
            remaining_epochs=self.epochs - self.epoch,
            minibatches_per_epoch=self.steps_per_epoch,
            remaining_in_epoch=self.steps_per_epoch - self.minibatch,
            minibatch_time=self.minibatch_time(),
            remaining_in_minibatch=rem_t)


@dataclass(frozen=True)
class UnitEvent:
    """One executed shard unit, reported through ``SharpExecutor.run``'s
    ``on_unit`` hook — the seam where a Session ticks serve engines between
    train shard-units and where plan/execute equivalence is audited."""
    model_id: int
    shard_index: int
    direction: str
    minibatch: int
    epoch: int
    device: int
    start: float
    end: float

    def key(self) -> tuple:
        """Schedule identity (virtual timestamps excluded: they shift with
        measured runtimes, the discrete assignment is the schedule)."""
        return (self.model_id, self.shard_index, self.direction,
                self.minibatch, self.epoch, self.device)


@dataclass
class RunReport:
    """What ``SharpExecutor.run`` did.  ``makespan``, ``utilization``,
    ``avg_utilization`` and the exposed/hidden transfer times come from
    the executor's virtual clock (measured pilot runtimes, or
    ``fixed_unit_runtime``, and transfers priced at ``link_bw``): they are
    modelled, not measured.  ``transfer`` counts the bytes really moved,
    ``wall_time`` is the host clock.  The measured per-phase timeline is
    the profiler trace of the run's host spans (docs/architecture.md,
    "Tracing")."""
    makespan: float
    utilization: dict[int, float]
    avg_utilization: float
    losses: dict[int, list]
    transfer: dict[int, Any]
    exposed_transfer_time: float
    hidden_transfer_time: float
    units_executed: int
    wall_time: float


class SharpExecutor:
    """Event-driven SHARP loop over virtual devices with real JAX compute."""

    def __init__(self, hydra_cfg: HydraConfig, models: list[ModelExec],
                 devices: Optional[list[DeviceMemory]] = None):
        self.hc = hydra_cfg
        self.models = models
        # caller-owned ledgers (repro.api.Session) let serving KV pages and
        # train double-buffers charge the SAME byte budget; standalone use
        # keeps private per-device ledgers
        self.devices = devices if devices is not None else [
            DeviceMemory(d, hydra_cfg.device_budget_bytes,
                         hydra_cfg.buffer_frac)
            for d in range(hydra_cfg.n_devices)]
        if len(self.devices) != hydra_cfg.n_devices:
            raise ValueError(
                f"{len(self.devices)} DeviceMemory ledgers for "
                f"{hydra_cfg.n_devices} devices")
        self.pick = sched.get_scheduler(hydra_cfg.scheduler,
                                        seed=hydra_cfg.seed)
        self.exposed_transfer = 0.0
        self.hidden_transfer = 0.0
        self.units_executed = 0
        # without SHARP, models run one-at-a-time (spilling-only mode)
        self.active_model: Optional[int] = None

    # -- pilot measurement --------------------------------------------------
    def pilot_pass(self):
        """Warm up all compiled programs and record measured unit runtimes.

        Runs one mini-batch per model on *cloned* params (training state is
        untouched) — the JAX-native analogue of the paper's pilot runs, which
        also dynamically refreshes Sharded-LRTF's runtime table.
        """
        for m in self.models:
            batch = m.pilot_batch
            acts = {}
            act = {}
            cot = None
            for shard in m.partition.shards:
                own, shared, _ = m.store.promote_shard(shard)
                fwd = m.fns.fwd(shard)
                acts[shard.index] = act
                out = fwd(own, shared, act, batch)[0]       # compile run
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                out = fwd(own, shared, act, batch)[0]
                jax.block_until_ready(out)
                shard.fwd_runtime = max(time.perf_counter() - t0, 1e-7)
                act = out
            for shard in reversed(m.partition.shards):
                own, shared, _ = m.store.promote_shard(shard)
                bwd = m.fns.bwd(shard)
                ain = acts[shard.index]
                if shard.index == len(m.partition.shards) - 1:
                    res = bwd(own, shared, ain, batch)
                    jax.block_until_ready(res)
                    t0 = time.perf_counter()
                    res = bwd(own, shared, ain, batch)
                    jax.block_until_ready(res)
                    cot = res[-1]
                else:
                    res = bwd(own, shared, ain, cot, batch)
                    jax.block_until_ready(res)
                    t0 = time.perf_counter()
                    res = bwd(own, shared, ain, cot, batch)
                    jax.block_until_ready(res)
                    cot = res[-1]
                shard.bwd_runtime = max(time.perf_counter() - t0, 1e-7)
            for shard in m.partition.shards:
                shard.est_runtime = shard.fwd_runtime + shard.bwd_runtime

    # -- real unit execution -------------------------------------------------
    def _execute_unit(self, m: ModelExec, unit: Unit) -> int:
        """Run one unit; returns the bytes it fetched device -> host.  Each
        host phase is a profiler span (no span encloses another)."""
        shard = unit.shard
        batch = m.current_batch
        ids = dict(step=m.minibatch, model=m.model_id, shard=shard.index)
        with TraceAnnotation("spill.promote", dir=unit.direction, **ids):
            own, shared, opt_state = m.store.promote_shard(shard)
        fetched = 0
        if unit.direction == "fwd":
            act_in = {} if shard.index == 0 \
                else m.saved_acts[("exit", shard.index - 1)]
            # entry activation is the checkpoint this shard's backward reuses
            m.saved_acts[("entry", shard.index)] = act_in
            with TraceAnnotation("sharp.dispatch", dir="fwd", **ids):
                out, loss, counts = m.fns.fwd(shard)(own, shared, act_in,
                                                     batch)
            if counts is not None:
                m.pending_counts.append(counts)
            if shard.index == len(m.partition.shards) - 1:
                with TraceAnnotation("sharp.loss_read", dir="fwd", **ids):
                    m.losses.append(float(loss))
                # the loss read waited for every forward unit of the step:
                # their counters are on the device and small
                m.ready_counts += m.pending_counts
                m.pending_counts = []
            m.saved_acts[("exit", shard.index)] = out
        else:
            act_in = m.saved_acts[("entry", shard.index)]
            last = shard.index == len(m.partition.shards) - 1
            with TraceAnnotation("sharp.dispatch", dir="bwd", **ids):
                if last:
                    loss, g_own, g_shared, g_act = m.fns.bwd(shard)(
                        own, shared, act_in, batch)
                else:
                    g_own, g_shared, g_act = m.fns.bwd(shard)(
                        own, shared, act_in, m.saved_cot, batch)
            m.saved_cot = g_act
            shared_names = m.store.shard_shared_names(shard)
            if shared_names:
                with TraceAnnotation("spill.shared_grads", dir="bwd", **ids):
                    fetched += m.store.accumulate_shared_grads(
                        {n: g_shared.get(n) for n in shared_names})
            with TraceAnnotation("sharp.dispatch", dir="step", **ids):
                new_own, new_opt = m.fns._step(own, g_own, opt_state)
            with TraceAnnotation("spill.demote", dir="bwd", **ids):
                fetched += m.store.demote_shard(shard, new_own, new_opt)
            # free this shard's saved activations
            m.saved_acts.pop(("entry", shard.index), None)
            m.saved_acts.pop(("exit", shard.index), None)
        return fetched

    # -- event loop -----------------------------------------------------------
    def run(self, *, max_units: Optional[int] = None,
            on_unit: Optional[Callable[[UnitEvent], None]] = None
            ) -> RunReport:
        wall0 = time.perf_counter()
        for m in self.models:
            m.build_minibatch_queue()
        if self.hc.pilot:
            for m in self.models:
                m.pilot_batch = m.current_batch
            self.pilot_pass()
        if self.hc.fixed_unit_runtime is not None:
            # applied independently of the pilot so the pin also holds with
            # pilot=False (analytic runtime estimates)
            rt = self.hc.fixed_unit_runtime
            for m in self.models:
                for shard in m.partition.shards:
                    shard.fwd_runtime = shard.bwd_runtime = rt
                    shard.est_runtime = 2 * rt

        windows = self.hc.device_windows or {}
        dev_heap = [(max(0.0, windows.get(d, (0.0, None))[0]), d)
                    for d in range(self.hc.n_devices)]
        heapq.heapify(dev_heap)
        dev_busy = {d: 0.0 for d in range(self.hc.n_devices)}
        dev_prev_start = {d: 0.0 for d in range(self.hc.n_devices)}
        makespan = 0.0

        while True:
            live = [m for m in self.models if not m.done]
            if not live:
                break
            if not dev_heap:
                raise RuntimeError(
                    "all devices retired with models unfinished "
                    f"({len(live)} remaining) — widen device_windows")
            t, d = heapq.heappop(dev_heap)
            until = windows.get(d, (0.0, None))[1]
            if until is not None and t >= until:
                continue    # device retired (fault / elasticity shrink)
            eligible = self._eligible()
            if not eligible:
                future = [m.ready_at for m in live if m.next_unit() is not None]
                if not future:
                    break
                heapq.heappush(dev_heap, (max(min(future), t + 1e-9), d))
                continue
            progress = [m.progress() for m in eligible]
            m = eligible[self.pick(progress)]
            unit = m.next_unit()
            m.reserved = True

            # ---- timing model -------------------------------------------
            shard_bytes = m.store.shard_transfer_bytes(unit.shard)
            act_bytes = unit.shard.act_bytes // 4   # boundary act only
            move_act = m.act_location is not None and m.act_location != d
            tx_bytes = shard_bytes + (act_bytes if move_act else 0)
            tx_time = tx_bytes / self.hc.link_bw
            if self.hc.enable_double_buffer:
                # transfer began when this device started its previous unit
                tx_start = max(dev_prev_start[d], m.ready_at)
                tx_end = tx_start + tx_time
                start = max(t, m.ready_at, tx_end)
                self.hidden_transfer += min(tx_time, max(0.0, t - tx_start))
                self.exposed_transfer += max(0.0, tx_end - max(t, m.ready_at))
            else:
                tx_start = max(t, m.ready_at)
                tx_end = tx_start + tx_time
                start = tx_end
                self.exposed_transfer += tx_time
            duration = unit.shard.fwd_runtime if unit.direction == "fwd" \
                else unit.shard.bwd_runtime
            end = start + duration

            # ---- memory accounting --------------------------------------
            dev = self.devices[d]
            dev.promote_through_buffer(
                shard_bytes, double_buffer=self.hc.enable_double_buffer)
            if move_act:
                dev.charge_act(act_bytes)

            # ---- real compute --------------------------------------------
            copied = m.store.host_copied_bytes
            fetched = self._execute_unit(m, unit)
            self.units_executed += 1
            dev.charge_demotion(shard_bytes, moved=fetched)
            while m.ready_counts:
                dev.stats.add_counters(m.ready_counts.pop(0))
            if on_unit is not None:
                on_unit(UnitEvent(
                    model_id=m.model_id, shard_index=unit.shard.index,
                    direction=unit.direction, minibatch=unit.minibatch,
                    epoch=unit.epoch, device=d, start=start, end=end))

            # ---- advance model state -------------------------------------
            m.cursor += 1
            m.ready_at = end
            m.reserved = False
            m.act_location = d
            if m.cursor >= len(m.queue):
                dev.charge_fetch(self._finish_minibatch(m))
            dev.stats.host_copied_bytes += m.store.host_copied_bytes - copied
            if not self.hc.enable_sharp and m.done and \
                    self.active_model == m.model_id:
                self.active_model = None

            dev_busy[d] += duration
            dev_prev_start[d] = start
            makespan = max(makespan, end)
            heapq.heappush(dev_heap, (end, d))
            if max_units is not None and self.units_executed >= max_units:
                break

        util = {d: (dev_busy[d] / makespan if makespan > 0 else 0.0)
                for d in dev_busy}
        return RunReport(
            makespan=makespan,
            utilization=util,
            avg_utilization=float(np.mean(list(util.values()))),
            losses={m.model_id: m.losses for m in self.models},
            transfer={dv.device_id: dv.stats for dv in self.devices},
            exposed_transfer_time=self.exposed_transfer,
            hidden_transfer_time=self.hidden_transfer,
            units_executed=self.units_executed,
            wall_time=time.perf_counter() - wall0)

    def _eligible(self) -> list[ModelExec]:
        live = [m for m in self.models
                if not m.done and not m.reserved and m.next_unit() is not None]
        if self.hc.enable_sharp:
            return live
        # spilling-only: one model at a time (paper Table 3 top row)
        if self.active_model is None and live:
            self.active_model = min(m.model_id for m in live)
        return [m for m in live if m.model_id == self.active_model]

    def _finish_minibatch(self, m: ModelExec) -> int:
        """Step the shared params and advance ``m`` to its next mini-batch;
        returns the bytes the shared step fetched device -> host."""
        with TraceAnnotation("spill.shared_step", step=m.minibatch,
                             model=m.model_id):
            fetched = m.store.step_shared()
        m.saved_acts.clear()
        m.saved_cot = None
        m.act_location = None
        m.minibatch += 1
        if m.minibatch >= m.steps_per_epoch:
            m.minibatch = 0
            m.epoch += 1
        # AutoML early stopping (Hyperband-class): underperformers leave the
        # workload — this is exactly the case-1 -> case-2 degradation
        # Sharded-LRTF is designed to handle gracefully (paper §4.7.2)
        if m.early_stop is not None and m.early_stop(m.losses):
            m.stopped_early = True
            m.done = True
        if m.epoch >= m.epochs:
            m.done = True
        if m.done:
            if not self.hc.enable_sharp and self.active_model == m.model_id:
                self.active_model = None
            return fetched
        m.build_minibatch_queue()
        return fetched
