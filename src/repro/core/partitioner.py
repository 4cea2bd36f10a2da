"""Automated model partitioning (paper §4.3, Algorithm 1).

Greedy, dynamic: pack the longest prefix of remaining segments that fits the
device memory budget.  Two fitting oracles:

* ``analytic`` (default) — a memory cost model over the segment's actual
  param trees: params + grads + optimizer state + boundary activations +
  recompute workspace.  Zero compile cost.
* ``probe`` — the paper's "pilot run", adapted to JAX AOT: lower + compile
  the shard's forward+backward on ShapeDtypeStructs and read
  ``memory_analysis()`` (no allocation, honest peak).  Used when the cost
  model would be too coarse (validated against it in tests).

The partitioner also records per-shard pilot *runtimes* (real measurements
when ``measure=True``) — these feed Sharded-LRTF exactly as in the paper.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import shard_graph as sg


@dataclass
class Shard:
    index: int
    seg_lo: int                    # [seg_lo, seg_hi) into plan.segments
    seg_hi: int
    param_bytes: int = 0
    act_bytes: int = 0
    est_runtime: float = 0.0       # seconds, fwd+bwd (pilot)
    fwd_runtime: float = 0.0
    bwd_runtime: float = 0.0

    @property
    def n_segments(self) -> int:
        return self.seg_hi - self.seg_lo


@dataclass
class PartitionResult:
    shards: list[Shard]
    shared_bytes: int
    budget_bytes: int
    oracle: str

    def __iter__(self):
        return iter(self.shards)

    def __len__(self):
        return len(self.shards)


# ---------------------------------------------------------------------------
# memory accounting
# ---------------------------------------------------------------------------

def tree_bytes(tree) -> int:
    return sum(np.asarray(x).nbytes if not hasattr(x, "nbytes") else x.nbytes
               for x in jax.tree.leaves(tree))


def _act_width(cfg) -> int:
    """Bytes per (batch·seq) element of the inter-segment activation; for
    a latent-attention block, of its widest live set instead: q, k and v
    in f32 (its scores are blockwise, its expert buffer narrower)."""
    if cfg.family == "mla_moe":
        return cfg.n_heads * (2 * cfg.head_dim + cfg.v_head_dim) * 4
    w = cfg.d_model * jnp.dtype(cfg.dtype).itemsize
    if cfg.family == "audio":
        w *= 2     # decoder segments also carry enc passthrough
    return w


def segment_cost(cfg, params, seg: sg.Segment, batch: int, seq: int,
                 *, train: bool = True) -> tuple[int, int]:
    """Returns (param_bytes, peak_act_bytes) for one segment."""
    own = sg.resolve_ref(params, seg.param_ref)
    pbytes = tree_bytes(own) if own is not None else 0
    opt_mult = 4 if train else 1        # params + grads + adam(mu, nu)
    act = batch * seq * _act_width(cfg)
    if seg.name in ("embed", "head", "frontend"):
        # head materializes logits in f32
        act = max(act, batch * seq * cfg.vocab_size * 4 // 8)  # sharded est.
    # remat inside segments: workspace ~ 4 live activation copies
    return pbytes * opt_mult, act * 4


def shared_cost(cfg, params, plan: sg.ShardPlan, *, train: bool = True) -> int:
    total = 0
    for name, ref in plan.shared_refs.items():
        total += tree_bytes(sg.resolve_ref(params, ref))
    return total * (4 if train else 1)


# ---------------------------------------------------------------------------
# fitting oracles
# ---------------------------------------------------------------------------

def analytic_fits(cfg, params, plan, lo, hi, batch, seq, budget, shared_bytes,
                  buffer_frac: float, train: bool = True) -> bool:
    total = shared_bytes
    for i in range(lo, hi):
        p, a = segment_cost(cfg, params, plan.segments[i], batch, seq,
                            train=train)
        total += p
        peak_act = a
    total += peak_act
    return total <= budget * (1.0 - buffer_frac)


def probe_fits(cfg, params, plan, lo, hi, batch, seq, budget, shared_bytes,
               buffer_frac: float, train: bool = True) -> bool:
    """AOT pilot-run: compile the shard's fwd+bwd, read memory_analysis.

    The JAX analogue of the paper's Algorithm-1 toy run: no allocation, but
    the honest compiled peak for this candidate shard."""
    own_spec, shared_spec = _shard_param_specs(cfg, params, plan, lo, hi)
    act_spec = _entry_act_spec(cfg, plan, lo, batch, seq)
    batch_spec = _batch_spec(cfg, batch, seq)

    def chain(own, shared, act, b):
        for k, i in enumerate(range(lo, hi)):
            seg = plan.segments[i]
            seg_shared = {n: shared[n] for n in seg.shared}
            act = seg.apply(cfg, own[k], seg_shared, act, b)
        return act

    def fwd_bwd(own, shared, act, b):
        out, vjp = jax.vjp(lambda o, s, a: chain(o, s, a, b),
                           own, shared, act)
        cots = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), out)
        return vjp(cots)

    try:
        compiled = jax.jit(fwd_bwd).lower(
            own_spec, shared_spec, act_spec, batch_spec).compile()
        ma = compiled.memory_analysis()
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes)
        # optimizer state for the shard also lives on device at step time
        opt_bytes = 2 * sum(
            tree_bytes(p) for p in
            (sg.resolve_ref(params, plan.segments[i].param_ref)
             for i in range(lo, hi)) if p is not None)
        return peak + opt_bytes + shared_bytes // 2 <= \
            budget * (1.0 - buffer_frac)
    except Exception:
        return False


def _shard_param_specs(cfg, params, plan, lo, hi):
    own = tuple(sg.resolve_ref(params, plan.segments[i].param_ref)
                for i in range(lo, hi))
    shared_names = sorted({n for i in range(lo, hi)
                           for n in plan.segments[i].shared})
    shared = {n: sg.resolve_ref(params, plan.shared_refs[n])
              for n in shared_names}
    to_spec = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), t)
    return to_spec(own), to_spec(shared)


def _batch_spec(cfg, batch, seq):
    d = cfg.d_model
    out = {"labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if cfg.family == "audio":
        out["enc_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_len, d), jnp.bfloat16)
        out["tokens"] = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    elif cfg.takes_embeddings:
        out["embeds"] = jax.ShapeDtypeStruct((batch, seq, d), jnp.bfloat16)
    else:
        out["tokens"] = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return out


def _entry_act_spec(cfg, plan, lo, batch, seq):
    d = cfg.d_model
    if lo == 0:
        return {}
    spec = {"x": jax.ShapeDtypeStruct((batch, seq, d), cfg.dtype)}
    if cfg.family == "moe":
        spec["aux"] = {"lb": jax.ShapeDtypeStruct((), jnp.float32),
                       "z": jax.ShapeDtypeStruct((), jnp.float32)}
    if cfg.family == "audio":
        spec = {"enc_x": jax.ShapeDtypeStruct(
            (batch, cfg.encoder_len, d), cfg.dtype)}
    return spec


# ---------------------------------------------------------------------------
# Algorithm 1 (greedy dynamic partitioning)
# ---------------------------------------------------------------------------

def partition(cfg, params, plan: sg.ShardPlan, *,
              budget_bytes: int,
              batch: int, seq: int,
              oracle: str = "analytic",
              buffer_frac: float = 0.05,
              train: bool = True,
              measure: bool = False,
              measure_batch=None,
              cost_model=None) -> PartitionResult:
    """Greedy prefix packing of segments into shards under ``budget_bytes``.

    ``buffer_frac`` reserves the double-buffer loading zone (paper §4.6:
    ~5% of device memory suffices since intermediates dominate and are not
    double-buffered).
    """
    fits = analytic_fits if oracle == "analytic" else probe_fits
    shared_bytes = shared_cost(cfg, params, plan, train=train)
    n = len(plan.segments)
    shards: list[Shard] = []
    lo = 0
    while lo < n:
        hi = lo + 1
        if not fits(cfg, params, plan, lo, hi, batch, seq, budget_bytes,
                    shared_bytes, buffer_frac, train):
            raise MemoryError(
                f"segment {plan.segments[lo].name} alone exceeds the device "
                f"budget ({budget_bytes/1e9:.2f} GB) — model unpartitionable")
        while hi < n and fits(cfg, params, plan, lo, hi + 1, batch, seq,
                              budget_bytes, shared_bytes, buffer_frac,
                              train):
            hi += 1
        pbytes = sum(segment_cost(cfg, params, plan.segments[i],
                                  batch, seq)[0] for i in range(lo, hi))
        abytes = max(segment_cost(cfg, params, plan.segments[i],
                                  batch, seq)[1] for i in range(lo, hi))
        shards.append(Shard(len(shards), lo, hi,
                            param_bytes=pbytes, act_bytes=abytes))
        lo = hi

    result = PartitionResult(shards, shared_bytes, budget_bytes, oracle)
    _assign_runtimes(cfg, params, plan, result,
                     cost_model=cost_model, batch=batch, seq=seq)
    return result


def _assign_runtimes(cfg, params, plan, result, *, cost_model=None,
                     batch: int = 2, seq: int = 128):
    """Initial runtime estimates ∝ flops_weight × param bytes.

    The SHARP executor's pilot pass (first mini-batch) overwrites these with
    *measured* per-shard times — a dynamic refinement of the paper's static
    pilot run; Sharded-LRTF reads whichever is current.

    With a ``repro.profiler.CostModel`` the same per-shard weights price
    against a *measured* whole-model forward instead of the analytic
    1e-12 s/weighted-byte prior; the unprofiled CostModel reproduces the
    analytic numbers byte-identically (and records either way in its
    provenance).
    """
    weights = [
        sum(plan.segments[i].flops_weight
            * max(1, sg_param_bytes(params, plan.segments[i]))
            for i in range(shard.seg_lo, shard.seg_hi))
        for shard in result.shards]
    if cost_model is not None:
        runtimes = cost_model.shard_runtimes(cfg, weights,
                                             batch=batch, seq=seq)
    else:
        runtimes = [(w * 1e-12, 2 * (w * 1e-12)) for w in weights]
    for shard, (fwd, bwd) in zip(result.shards, runtimes):
        shard.fwd_runtime = fwd
        shard.bwd_runtime = bwd
        shard.est_runtime = shard.fwd_runtime + shard.bwd_runtime


def sg_param_bytes(params, seg) -> int:
    own = sg.resolve_ref(params, seg.param_ref)
    return tree_bytes(own) if own is not None else 0
